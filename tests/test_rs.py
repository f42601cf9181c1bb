"""Reed–Solomon GF(2⁸) kernel + erasure-coded segment protection.

Three-way equivalence (numpy table reference ↔ XLA fallback ↔ Pallas
kernel in interpret mode), field/MDS properties, and the storage wiring:
any 2-of-5 shard loss rebuilds a sealed segment byte-for-byte. The
reference has no erasure coding at all (it full-replicates through JRaft)
— this is SURVEY.md §7 step 6 / BASELINE.json config #4.
"""

import itertools
import os
import zlib

import numpy as np
import pytest

from ripplemq_tpu.ops import rs
from ripplemq_tpu.storage import erasure
from ripplemq_tpu.storage.segment import REC_APPEND, SegmentStore, scan_store


# ---------------------------------------------------------------- field math


def test_gf_field_properties():
    rng = np.random.default_rng(0)
    for _ in range(200):
        a, b, c = (int(x) for x in rng.integers(0, 256, 3))
        assert rs.gf_mul(a, b) == rs.gf_mul(b, a)
        assert rs.gf_mul(a, rs.gf_mul(b, c)) == rs.gf_mul(rs.gf_mul(a, b), c)
        # distributive over XOR (field addition)
        assert rs.gf_mul(a, b ^ c) == rs.gf_mul(a, b) ^ rs.gf_mul(a, c)
    for a in range(1, 256):
        assert rs.gf_mul(a, rs.gf_inv(a)) == 1
    with pytest.raises(ZeroDivisionError):
        rs.gf_inv(0)


def test_extended_matrix_is_mds():
    """Every k-row submatrix of [I; C] must be invertible — the property
    that makes ANY 3-of-5 shards sufficient."""
    ext = rs.extended_matrix(3, 2)
    for rows in itertools.combinations(range(5), 3):
        inv = rs.gf_invert([ext[r] for r in rows])
        # verify inv really is the inverse
        for i in range(3):
            for j in range(3):
                got = 0
                for t in range(3):
                    got ^= rs.gf_mul(inv[i][t], ext[rows[t]][j])
                assert got == (1 if i == j else 0)


def test_gf_invert_rejects_singular():
    with pytest.raises(ValueError):
        rs.gf_invert([(1, 2), (1, 2)])


# ------------------------------------------------------- 3-way equivalence


@pytest.mark.parametrize("n", [1, 7, 128, 1000, 4096, 5000])
def test_matmul_equivalence_xla_pallas_numpy(n):
    rng = np.random.default_rng(n)
    shards = rng.integers(0, 256, size=(3, n), dtype=np.uint8)
    coeffs = rs.generator_matrix(3, 2)
    ref = rs.gf_matmul_ref(coeffs, shards)
    xla = np.asarray(rs.gf_matmul(coeffs, shards, use_pallas=False))
    pal = np.asarray(
        rs.gf_matmul(coeffs, shards, use_pallas=False, interpret=True)
    )
    assert np.array_equal(xla, ref)
    assert np.array_equal(pal, ref)


def test_matmul_identity_and_zero_rows():
    rng = np.random.default_rng(3)
    shards = rng.integers(0, 256, size=(2, 600), dtype=np.uint8)
    out = np.asarray(
        rs.gf_matmul(((1, 0), (0, 1), (0, 0)), shards, use_pallas=False)
    )
    assert np.array_equal(out[0], shards[0])
    assert np.array_equal(out[1], shards[1])
    assert not out[2].any()


def test_matmul_validates_shapes():
    with pytest.raises(ValueError):
        rs.gf_matmul(((1, 2),), np.zeros((3, 8), np.uint8))


# ----------------------------------------------------------- reconstruction


def test_any_two_losses_reconstruct():
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(3, 999), dtype=np.uint8)
    parity = np.asarray(rs.rs_encode(data, use_pallas=False))
    shards = np.concatenate([data, parity], axis=0)
    for lost in itertools.combinations(range(5), 2):
        present = {
            i: shards[i] for i in range(5) if i not in lost
        }
        rec = np.asarray(rs.rs_reconstruct(present, use_pallas=False))
        assert np.array_equal(rec, data), f"lost {lost}"


def test_reconstruct_needs_k_shards():
    with pytest.raises(ValueError):
        rs.rs_reconstruct({0: np.zeros(8, np.uint8), 4: np.zeros(8, np.uint8)})


# -------------------------------------------------------- segment protection


def _fill_store(tmp_path, rounds=40, segment_bytes=4096):
    store_dir = str(tmp_path / "segments")
    store = SegmentStore(store_dir, segment_bytes=segment_bytes,
                         use_native=False)
    payloads = {}
    for i in range(rounds):
        payload = os.urandom(256)
        store.append(REC_APPEND, i % 4, i, payload)
        payloads[i] = payload
    store.close()
    return store_dir, payloads


def _scan_all(store_dir):
    return list(scan_store(store_dir, use_native=False))


def test_protect_and_repair_lost_segment(tmp_path):
    store_dir, _ = _fill_store(tmp_path)
    before = _scan_all(store_dir)
    sealed = erasure._segment_names(store_dir)[:-1]
    assert len(sealed) >= 2, "test needs multiple sealed segments"
    assert erasure.protect_store(store_dir) == sealed

    # Destroy one sealed segment entirely and corrupt another.
    os.remove(os.path.join(store_dir, sealed[0]))
    with open(os.path.join(store_dir, sealed[1]), "r+b") as f:
        f.seek(17)
        f.write(b"\xde\xad\xbe\xef")

    assert sorted(erasure.repair_store(store_dir)) == sorted(sealed[:2])
    assert _scan_all(store_dir) == before


def test_repair_survives_any_two_shard_losses(tmp_path):
    store_dir, _ = _fill_store(tmp_path, rounds=12, segment_bytes=1024)
    before = _scan_all(store_dir)
    sealed = erasure._segment_names(store_dir)[:-1]
    erasure.protect_store(store_dir)
    name = sealed[0]
    seg_path = os.path.join(store_dir, name)
    with open(seg_path, "rb") as f:
        seg_bytes = f.read()
    for lost in itertools.combinations(range(5), 2):
        paths = erasure.shard_paths(store_dir, name)
        saved = {}
        for i in lost:
            with open(paths[i], "rb") as f:
                saved[i] = f.read()
            os.remove(paths[i])
        os.remove(seg_path)
        assert erasure.repair_store(store_dir) == [name]
        with open(seg_path, "rb") as f:
            assert f.read() == seg_bytes, f"lost shards {lost}"
        for i, blob in saved.items():
            with open(paths[i], "wb") as f:
                f.write(blob)
    assert _scan_all(store_dir) == before


def test_three_shard_losses_fail_cleanly(tmp_path):
    store_dir, _ = _fill_store(tmp_path, rounds=12, segment_bytes=1024)
    sealed = erasure._segment_names(store_dir)[:-1]
    erasure.protect_store(store_dir)
    name = sealed[0]
    paths = erasure.shard_paths(store_dir, name)
    for i in range(3):
        os.remove(paths[i])
    os.remove(os.path.join(store_dir, name))
    with pytest.raises(erasure.ShardError):
        erasure.reconstruct_segment(store_dir, name)


def test_corrupt_shard_is_rejected_not_used(tmp_path):
    """A bit-flipped shard must fail its CRC and be excluded; repair
    still succeeds from the remaining 4."""
    store_dir, _ = _fill_store(tmp_path, rounds=12, segment_bytes=1024)
    sealed = erasure._segment_names(store_dir)[:-1]
    erasure.protect_store(store_dir)
    name = sealed[0]
    seg_path = os.path.join(store_dir, name)
    with open(seg_path, "rb") as f:
        seg_bytes = f.read()
    shard0 = erasure.shard_paths(store_dir, name)[0]
    with open(shard0, "r+b") as f:
        f.seek(erasure._HEADER.size + 3)
        f.write(b"\xff\xff")
    os.remove(seg_path)
    assert erasure.repair_store(store_dir) == [name]
    with open(seg_path, "rb") as f:
        assert f.read() == seg_bytes


def test_empty_segment_and_empty_matmul_are_safe(tmp_path):
    """A restart leaves a 0-byte sealed segment (both store backends open
    a fresh index on boot); protect must skip it forever instead of
    crashing the flush path, and gf_matmul(n=0) must not divide by
    zero."""
    out = np.asarray(rs.gf_matmul(rs.generator_matrix(3, 2),
                                  np.zeros((3, 0), np.uint8)))
    assert out.shape == (2, 0)
    store_dir = str(tmp_path / "segments")
    os.makedirs(store_dir)
    open(os.path.join(store_dir, "segment-00000000.log"), "wb").close()
    with open(os.path.join(store_dir, "segment-00000001.log"), "wb") as f:
        f.write(b"x" * 64)
    assert erasure.protect_store(store_dir) == []  # only seg 1 is active
    assert erasure._shard_counts(store_dir) == {}


def test_partial_shard_set_is_reencoded_by_protect(tmp_path):
    """A crash mid-encode leaves < k+m shards; protect_store must treat
    the segment as unprotected and re-encode the full set."""
    store_dir, _ = _fill_store(tmp_path, rounds=12, segment_bytes=1024)
    sealed = erasure._segment_names(store_dir)[:-1]
    erasure.protect_store(store_dir)
    name = sealed[0]
    paths = erasure.shard_paths(store_dir, name)
    for p in paths[1:]:
        os.remove(p)  # simulate crash after writing shard 0
    assert name in erasure.protect_store(store_dir)
    assert all(os.path.exists(p) for p in paths)


def test_repair_skips_unrecoverable_sets_without_raising(tmp_path):
    """Segment gone + 3 of 5 shards gone (> m losses): repair must leave
    it to the scanner, not raise ShardError into broker boot."""
    store_dir, _ = _fill_store(tmp_path, rounds=12, segment_bytes=1024)
    sealed = erasure._segment_names(store_dir)[:-1]
    erasure.protect_store(store_dir)
    name = sealed[0]
    os.remove(os.path.join(store_dir, name))
    for p in erasure.shard_paths(store_dir, name)[:3]:
        os.remove(p)
    assert erasure.repair_store(store_dir) == []  # no crash, nothing fixed


def test_segmentstore_flush_protects_and_recovery_repairs(tmp_path):
    """End-to-end through the store API: erasure=True encodes sealed
    segments on flush; recover_image's repair path heals a deleted sealed
    segment before replay."""
    from ripplemq_tpu.broker.dataplane import recover_image
    from tests.helpers import small_cfg

    store_dir = str(tmp_path / "segments")
    cfg = small_cfg()
    store = SegmentStore(store_dir, segment_bytes=1024, use_native=False,
                         erasure=True)
    SB = cfg.slot_bytes
    import struct as _s
    for i in range(8):
        rows = np.zeros((8, SB), np.uint8)
        payload = b"seal-%03d" % i
        rows[0, :4] = np.frombuffer(_s.pack("<i", len(payload)), np.uint8)
        rows[0, 4:8] = np.frombuffer(_s.pack("<i", 1), np.uint8)
        rows[0, 8 : 8 + len(payload)] = np.frombuffer(payload, np.uint8)
        store.append(REC_APPEND, 0, i * 8, rows.tobytes())
        store.flush()
    store.close()
    sealed = erasure._segment_names(store_dir)[:-1]
    assert sealed and erasure._protected_names(store_dir) >= set(sealed)

    image_before = recover_image(cfg, store_dir, use_native=False)
    os.remove(os.path.join(store_dir, sealed[-1]))
    image_after = recover_image(cfg, store_dir, use_native=False)
    assert image_after is not None
    np.testing.assert_array_equal(
        np.asarray(image_before.log_data), np.asarray(image_after.log_data)
    )


def test_seal_encode_is_timed_and_new_shard_lengths_counted(tmp_path):
    """ISSUE 24: with a registry the erasure worker times each sealed
    segment's RS encode (`seal.rs_encode_us`) and counts the encodes
    that met a shard length this store had not seen (`rs.new_shapes`:
    the length is a static jit argument, so each is a new program);
    without one it times nothing."""
    from ripplemq_tpu.obs.metrics import Metrics

    m = Metrics()
    store = SegmentStore(str(tmp_path / "timed"), segment_bytes=1024,
                         use_native=False, erasure=True, metrics=m)
    sizes = (700, 700, 900, 700)  # three sealed segments, two lengths
    for i, n in enumerate(sizes):
        store.append(REC_APPEND, 0, i * 8, bytes(n))
        store.append(REC_APPEND, 0, i * 8 + 4, bytes(400))  # rotates
    store.close()  # joins the worker, then encodes what is left
    sealed = erasure._segment_names(store.directory)[:-1]
    assert erasure._protected_names(store.directory) >= set(sealed)
    lengths = {-(-os.path.getsize(os.path.join(store.directory, s)) // 3)
               for s in sealed if os.path.getsize(
                   os.path.join(store.directory, s))}
    snap = m.snapshot()
    encoded = snap["histograms"]["seal.rs_encode_us"]["count"]
    assert encoded >= len(lengths) >= 2
    assert snap["counters"]["rs.new_shapes"] == len(lengths)

    bare = SegmentStore(str(tmp_path / "bare"), segment_bytes=1024,
                        use_native=False, erasure=True)
    bare.append(REC_APPEND, 0, 0, bytes(700))
    bare.append(REC_APPEND, 0, 8, bytes(700))
    bare.close()
    assert erasure._protected_names(bare.directory)
