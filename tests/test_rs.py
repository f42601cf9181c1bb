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


def test_seal_encode_is_timed_and_new_shard_buckets_counted(tmp_path):
    """ISSUE 24, 26: with a registry the erasure worker times each sealed
    segment's RS encode (`seal.rs_encode_us`) and counts the shard
    length BUCKETS this store has asked a program for (`rs.new_shapes`);
    lengths that share a bucket share a program. Without a registry it
    times nothing."""
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
    assert len({rs.shard_bucket(n) for n in lengths}) == 1
    assert snap["counters"]["rs.new_shapes"] == 1

    bare = SegmentStore(str(tmp_path / "bare"), segment_bytes=1024,
                        use_native=False, erasure=True)
    bare.append(REC_APPEND, 0, 0, bytes(700))
    bare.append(REC_APPEND, 0, 8, bytes(700))
    bare.close()
    assert erasure._protected_names(bare.directory)


# ------------------------------------------------- the shard-length bucket

_BLOCK = rs._BLOCK_ROWS * rs._PACK
_S = 64 << 20  # the default segment_bytes
_FRAME = 8 << 20  # a standby's group-commit frame, the largest one write


def test_shard_bucket_is_a_monotone_ladder_of_whole_blocks():
    rng = np.random.default_rng(26)
    ns = np.unique(np.concatenate([
        np.arange(0, 4 * _BLOCK + 2, 509),
        [(1 << k) + d for k in range(18, 33) for d in (-1, 0, 1)],
        rng.integers(1, 1 << 32, 4000),
    ]))
    bs = [rs.shard_bucket(int(n)) for n in ns]
    assert bs == sorted(bs)
    for n, b in zip(ns.tolist(), bs):
        assert b >= n and b % _BLOCK == 0
        assert rs.shard_bucket(b) == b  # an entry is its own bucket
        # never a whole step over: one block up to 2^20, then a quarter
        # of the power of two below n (at most 25%)
        assert b - n < max(_BLOCK, n // 4 + 1)
    assert rs.shard_bucket(0) == 0 and rs.shard_bucket(1) == _BLOCK
    assert [rs.shard_bucket((1 << 24) + 1 + i * (1 << 22)) >> 20
            for i in range(4)] == [20, 24, 28, 32]


@pytest.mark.parametrize("lo,hi", [
    (_S, _S + _FRAME),       # ISSUE 26's range: a write past segment_bytes
    (_S - (4 << 20) + 1, _S),  # what the writers do: a write short of it
])
def test_default_segments_meet_one_bucket(lo, hi):
    # the ladder is monotone (above), so the two ends speak for the range
    assert rs.shard_bucket(-(-lo // 3)) == rs.shard_bucket(-(-hi // 3)) \
        == 24 << 20


@pytest.mark.parametrize("segment_bytes", [
    1024, 4096, 32768, 1 << 20, 8 << 20, 48 << 20, 61 << 20, 1 << 30])
def test_any_segment_size_meets_two_buckets_at_most(segment_bytes):
    """A sealed segment is at most one write short of segment_bytes; a
    write is a round's records or a group-commit frame, taken here as up
    to a sixteenth of the segment and never over 8 MiB. Monotone, so:
    the full segment's entry is the shortest one's or the next. The
    tests' small stores fit one kernel block whatever they hold."""
    short = min(segment_bytes // 16, _FRAME)
    lo = rs.shard_bucket(-(-(segment_bytes - short) // 3))
    assert rs.shard_bucket(-(-segment_bytes // 3)) in (
        lo, rs.shard_bucket(lo + 1))
    if segment_bytes <= 3 * _BLOCK:
        assert rs.shard_bucket(-(-2 * segment_bytes // 3)) == _BLOCK


@pytest.mark.parametrize("rows", [1, 2, 3, 8, 9, 64, 511, 512, 513])
def test_bucketed_parity_matches_reference_at_block_edges(rows):
    """Lengths around whole packed rows, one block (512 rows) and one row
    into the second block: both device forms against the numpy tables."""
    coeffs = rs.generator_matrix(3, 2)
    rng = np.random.default_rng(rows)
    for n in (rows * rs._PACK - 1, rows * rs._PACK):
        shards = rng.integers(0, 256, size=(3, n), dtype=np.uint8)
        ref = rs.gf_matmul_ref(coeffs, shards)
        xla = rs.gf_matmul(coeffs, shards, use_pallas=False)
        pal = rs.gf_matmul(coeffs, shards, use_pallas=False, interpret=True)
        assert xla.shape == pal.shape == (2, n)
        assert np.array_equal(xla, ref) and np.array_equal(pal, ref)


def test_segment_scale_parity_matches_reference():
    """One shard of a full default segment (chip_smoke.py's size)."""
    n = -(-_S // 3)
    assert n == 22_369_622
    shards = np.random.default_rng(7).integers(
        0, 256, size=(3, n), dtype=np.uint8)
    coeffs = rs.generator_matrix(3, 2)
    assert np.array_equal(rs.gf_matmul(coeffs, shards, use_pallas=False),
                          rs.gf_matmul_ref(coeffs, shards))


def test_every_erasure_pattern_reconstructs_on_a_bucketed_length():
    n = _BLOCK + 12_345  # padded to two blocks: the grid has two steps
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(3, n), dtype=np.uint8)
    shards = np.concatenate([data, rs.rs_encode(data, use_pallas=False)])
    for r in (0, 1, 2):
        for lost in itertools.combinations(range(5), r):
            present = {i: shards[i] for i in range(5) if i not in lost}
            rec = rs.rs_reconstruct(present, use_pallas=False)
            assert rec.shape == (3, n)
            assert np.array_equal(rec, data), f"lost {lost}"


def _seal(store, base, nbytes):
    """One record of `nbytes`, then the next: the second rotates."""
    store.append(REC_APPEND, 0, base, os.urandom(nbytes))


def test_five_lengths_one_program_compiled_at_open(tmp_path):
    """The store builds its RS program when it opens, on the erasure
    thread; five sealed segments of five lengths then compile nothing
    (`rs.new_shapes` stays 1, one entry in the jit cache) and only they
    are timed as seals."""
    from ripplemq_tpu.obs.metrics import Metrics

    rs._gf_matmul_jit.clear_cache()
    m = Metrics()
    store = SegmentStore(str(tmp_path / "s"), segment_bytes=4096,
                         use_native=False, erasure=True, metrics=m)
    store.wait_erasure(timeout=60)
    assert not store._erasure_thread.is_alive()
    assert m.snapshot()["counters"]["rs.new_shapes"] == 1
    assert rs._gf_matmul_jit._cache_size() == 1
    assert "seal.rs_encode_us" not in {
        k for k, h in m.snapshot()["histograms"].items() if h["count"]}
    sizes = (2100, 2500, 2900, 3300, 3700)
    for i, nbytes in enumerate(sizes):
        _seal(store, i * 8, nbytes)
    _seal(store, 99, 2100)  # rotates the fifth out
    store.close()
    sealed = erasure._segment_names(store.directory)[:-1]
    assert len({os.path.getsize(os.path.join(store.directory, s))
                for s in sealed}) == 5
    assert erasure._protected_names(store.directory) >= set(sealed)
    snap = m.snapshot()
    assert snap["histograms"]["seal.rs_encode_us"]["count"] == 5
    assert snap["counters"]["rs.new_shapes"] == 1
    assert rs._gf_matmul_jit._cache_size() == 1
    assert store.erasure_errors == []


def test_a_segment_far_short_of_its_size_runs_the_program_built_at_open(
        tmp_path):
    """A store whose writes are most of a segment long seals far short
    of segment_bytes - rotation comes before the write that would cross
    it, and one round of 1 KB rows can be many MiB (PR 33). Its shard
    length falls in the ladder entry below the one built at open; the
    store pads it up to that one, so nothing compiles beside traffic,
    and the shards still rebuild the segment."""
    from ripplemq_tpu.obs.metrics import Metrics

    m = Metrics()
    store = SegmentStore(str(tmp_path / "s"), segment_bytes=1 << 20,
                         use_native=False, erasure=True, metrics=m)
    for i, nbytes in enumerate((600_000, 610_000, 620_000, 630_000)):
        _seal(store, i * 8, nbytes)
    store.close()
    snap = m.snapshot()
    assert snap["histograms"]["seal.rs_encode_us"]["count"] == 3
    assert {rs.shard_bucket(-(-(1 << 20) // 3)),
            rs.shard_bucket(-(-600_040 // 3))} == {2 * _BLOCK, _BLOCK}
    assert snap["counters"]["rs.new_shapes"] == 1
    name = erasure._segment_names(store.directory)[0]
    with open(os.path.join(store.directory, name), "rb") as f:
        raw = f.read()
    for path in erasure.shard_paths(store.directory, name)[:2]:
        os.remove(path)
    assert erasure.reconstruct_segment(store.directory, name) == raw


def _parent_shard_blobs(raw: bytes) -> list[bytes]:
    """The shard files of one segment as the code before ISSUE 26 wrote
    them, from the numpy table reference: the segment zero-padded to
    K*n, cut in K rows, parity over exactly n columns."""
    import struct

    n = -(-len(raw) // 3)
    padded = np.zeros(3 * n, np.uint8)
    padded[: len(raw)] = np.frombuffer(raw, np.uint8)
    data = padded.reshape(3, n)
    rows = np.concatenate(
        [data, rs.gf_matmul_ref(rs.generator_matrix(3, 2), data)])
    crc = zlib.crc32(raw) & 0xFFFFFFFF
    return [
        struct.pack("<IBBBBQII", 0x52535348, 1, i, 3, 2, len(raw), crc,
                    zlib.crc32(rows[i].tobytes()) & 0xFFFFFFFF)
        + rows[i].tobytes()
        for i in range(5)
    ]


def test_shard_files_are_byte_identical_to_the_parents(tmp_path):
    """The bucket changes what the device is handed, not what is stored:
    every shard file equals the one the unbucketed code wrote, and a
    store whose shards that code wrote still verifies and repairs."""
    store_dir, _ = _fill_store(tmp_path, rounds=60, segment_bytes=4000)
    sealed = erasure._segment_names(store_dir)[:-1]
    assert len(sealed) >= 3
    erasure.protect_store(store_dir)
    want = {}
    for name in sealed:
        with open(os.path.join(store_dir, name), "rb") as f:
            want[name] = _parent_shard_blobs(f.read())
        for path, blob in zip(erasure.shard_paths(store_dir, name),
                              want[name]):
            with open(path, "rb") as f:
                assert f.read() == blob, path

    # a parent-written store: only its files, two shards and one segment lost
    old = str(tmp_path / "old")
    os.makedirs(os.path.join(old, "rs"))
    for name in erasure._segment_names(store_dir):
        with open(os.path.join(store_dir, name), "rb") as f:
            raw = f.read()
        with open(os.path.join(old, name), "wb") as f:
            f.write(raw)
    for name in sealed:
        for path, blob in zip(erasure.shard_paths(old, name), want[name]):
            with open(path, "wb") as f:
                f.write(blob)
    assert erasure.repair_store(old) == []  # every shard verifies
    before = _scan_all(old)
    victim = sealed[1]
    os.remove(os.path.join(old, victim))
    os.remove(erasure.shard_paths(old, victim)[0])
    os.remove(erasure.shard_paths(old, victim)[3])
    errors: list = []
    assert erasure.repair_store(old, errors=errors) == [victim]
    assert errors == [] and _scan_all(old) == before
    for path, blob in zip(erasure.shard_paths(old, victim), want[victim]):
        with open(path, "rb") as f:
            assert f.read() == blob, path
