"""Pallas append-kernel correctness (interpret mode vs the XLA fallback).

Round-1 gap: the hottest op in the system (`ops/append.py`) only ever
executed on real TPU inside bench.py, with no readback — a broken DMA
index would have passed CI and the bench. These tests run the SAME Pallas
kernel through the Mosaic interpreter against `append_rows_xla` over
randomized (base, do_write, entries) cases, pinning the semantics
contract documented in ops/append.py:21-27.
"""

import numpy as np
import pytest

from ripplemq_tpu.core.config import ALIGN
from ripplemq_tpu.ops.append import _append_pallas, append_rows, append_rows_xla


def rand_case(rng, R=3, P=8, S=64, SB=128, B=16):
    log = rng.integers(0, 256, size=(R, P, S, SB), dtype=np.uint8)
    entries = rng.integers(0, 256, size=(P, B, SB), dtype=np.uint8)
    # Contract: base is ALIGN-aligned and base + B <= S wherever do_write.
    base = (
        rng.integers(0, (S - B) // ALIGN + 1, size=(P,)) * ALIGN
    ).astype(np.int32)
    do_write = rng.random((R, P)) < 0.6
    return log, entries, base, do_write


@pytest.mark.parametrize("seed", range(8))
def test_pallas_interpret_matches_xla_randomized(seed):
    rng = np.random.default_rng(seed)
    log, entries, base, do_write = rand_case(rng)
    got = np.asarray(
        _append_pallas(log, entries, base, do_write, interpret=True)
    )
    want = np.asarray(append_rows_xla(log, entries, base, do_write))
    np.testing.assert_array_equal(got, want)


def test_pallas_interpret_odd_shapes():
    """P not divisible by the kernel's K-target, small SB, B == ALIGN."""
    rng = np.random.default_rng(99)
    log, entries, base, do_write = rand_case(rng, R=2, P=5, S=32, SB=32, B=8)
    got = np.asarray(
        _append_pallas(log, entries, base, do_write, interpret=True)
    )
    want = np.asarray(append_rows_xla(log, entries, base, do_write))
    np.testing.assert_array_equal(got, want)


def test_no_writes_is_identity():
    rng = np.random.default_rng(1)
    log, entries, base, _ = rand_case(rng)
    do_write = np.zeros((3, 8), bool)
    got = np.asarray(
        _append_pallas(log, entries, base, do_write, interpret=True)
    )
    np.testing.assert_array_equal(got, log)


def test_full_window_written_including_padding_rows():
    """The contract says the FULL B-row window lands whenever do_write —
    including rows past `count` (length-0 padding): the next round relies
    on overwriting stale bytes."""
    rng = np.random.default_rng(2)
    log, entries, base, _ = rand_case(rng)
    do_write = np.ones((3, 8), bool)
    got = np.asarray(
        _append_pallas(log, entries, base, do_write, interpret=True)
    )
    B = entries.shape[1]
    for p in range(8):
        b = int(base[p])
        for r in range(3):
            np.testing.assert_array_equal(got[r, p, b : b + B], entries[p])


def test_base_at_capacity_edge():
    """base + B == S exactly (the capacity rule's boundary)."""
    rng = np.random.default_rng(3)
    log, entries, _, do_write = rand_case(rng)
    S, B = log.shape[2], entries.shape[1]
    base = np.full((8,), S - B, np.int32)
    got = np.asarray(
        _append_pallas(log, entries, base, do_write, interpret=True)
    )
    want = np.asarray(append_rows_xla(log, entries, base, do_write))
    np.testing.assert_array_equal(got, want)


def test_dispatcher_interpret_flag_routes_to_pallas():
    rng = np.random.default_rng(4)
    log, entries, base, do_write = rand_case(rng)
    got = np.asarray(append_rows(log, entries, base, do_write, interpret=True))
    want = np.asarray(append_rows_xla(log, entries, base, do_write))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------- active-set write

def rand_sparse_case(rng, R=3, P=16, S=64, SB=128, B=16, A=8, actives=5):
    """Dense case + its compact active-set form for the same partitions."""
    log = rng.integers(0, 256, size=(R, P, S, SB), dtype=np.uint8)
    entries = np.zeros((P, B, SB), np.uint8)
    base = (
        rng.integers(0, (S - B) // ALIGN + 1, size=(P,)) * ALIGN
    ).astype(np.int32)
    do_write = np.zeros((R, P), bool)
    ids = np.full((A,), -1, np.int32)
    entries_c = np.zeros((A, B, SB), np.uint8)
    chosen = rng.choice(P, size=actives, replace=False)
    for a, p in enumerate(chosen):
        block = rng.integers(0, 256, size=(B, SB), dtype=np.uint8)
        entries[p] = block
        entries_c[a] = block
        ids[a] = p
        do_write[:, p] = rng.random(R) < 0.7
    return log, entries, entries_c, ids, base, do_write


@pytest.mark.parametrize("seed", range(8))
def test_active_set_matches_dense_randomized(seed):
    from ripplemq_tpu.ops.append import (
        _append_active_pallas,
        append_rows_active_xla,
    )

    rng = np.random.default_rng(seed)
    log, entries, entries_c, ids, base, do_write = rand_sparse_case(rng)
    dense = np.asarray(append_rows_xla(log.copy(), entries, base, do_write))
    got_xla = np.asarray(
        append_rows_active_xla(log.copy(), entries_c, ids, base, do_write)
    )
    got_pl = np.asarray(_append_active_pallas(
        log.copy(), entries_c, ids, base, do_write, interpret=True
    ))
    np.testing.assert_array_equal(got_xla, dense)
    np.testing.assert_array_equal(got_pl, dense)


def test_active_set_all_padding_is_identity():
    from ripplemq_tpu.ops.append import _append_active_pallas

    rng = np.random.default_rng(7)
    log, *_ = rand_sparse_case(rng)
    A, B, SB = 8, 16, 128
    got = np.asarray(_append_active_pallas(
        log.copy(), np.zeros((A, B, SB), np.uint8),
        np.full((A,), -1, np.int32),
        np.zeros((log.shape[1],), np.int32),
        np.ones((log.shape[0], log.shape[1]), bool),
        interpret=True,
    ))
    np.testing.assert_array_equal(got, log)


def test_pallas_uniform_fast_path_matches_xla():
    """The uniform fast path (all Ka partitions of a grid block active,
    consecutive, equal bases — one strided DMA instead of Ka) must be
    byte-identical to the XLA reference. The randomized cases above
    essentially never satisfy the predicate (per-partition random
    bases), so this pins the hottest branch explicitly: a dense round
    with every partition advancing in lockstep — the exact shape the
    headline bench drives."""
    rng = np.random.default_rng(7)
    R, P, S, SB, B = 3, 16, 64, 128, 16
    log = rng.integers(0, 256, size=(R, P, S, SB), dtype=np.uint8)
    entries = rng.integers(0, 256, size=(P, B, SB), dtype=np.uint8)
    base = np.full((P,), 2 * ALIGN, np.int32)   # equal bases everywhere
    do_write = np.ones((R, P), bool)            # all active
    got = np.asarray(
        _append_pallas(log, entries, base, do_write, interpret=True)
    )
    want = np.asarray(append_rows_xla(log, entries, base, do_write))
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------- extent classes
#
# The copy region is clipped to the round's extent, rounded UP to a
# power-of-two class of ALIGN-row blocks (both backends apply the same
# rule — ops/append.py extent-classes section). The Pallas kernel must
# stay bit-identical to the XLA fallback on the FULL log; against a
# full-window write (extents=None), rows below the extent class must
# match and rows above it must be untouched.

def _packed_rows_ref(extent, B):
    """Python reference of the class rule: smallest power-of-two block
    count >= ceil(extent/ALIGN), clamped to [1, B/ALIGN], in rows."""
    BA = B // ALIGN
    eb = min(max(-(-int(extent) // ALIGN), 1), BA)
    s = 1
    while s < eb:
        s *= 2
    return min(s, BA) * ALIGN


@pytest.mark.parametrize("seed,sb", [
    *((seed, 128) for seed in range(6)),
    (6, 1152), (7, 1152),  # omb-100p-1kb's row, nine lanes wide
])
def test_packed_pallas_matches_packed_xla_randomized(seed, sb):
    rng = np.random.default_rng(seed)
    log, entries, base, do_write = rand_case(rng, SB=sb)
    P, B = entries.shape[0], entries.shape[1]
    extents = (rng.integers(0, B // ALIGN + 1, size=(P,)) * ALIGN).astype(
        np.int32
    )
    got = np.asarray(_append_pallas(
        log, entries, base, do_write, extents=extents, interpret=True
    ))
    want = np.asarray(
        append_rows_xla(log, entries, base, do_write, extents)
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", range(4))
def test_extent_class_prefix_and_untouched_tail(seed):
    """Packed output == unpacked output on rows below each partition's
    extent class, and == the PRIOR log bytes above it (the packed mode's
    whole point: those bytes are never moved)."""
    rng = np.random.default_rng(100 + seed)
    log, entries, base, do_write = rand_case(rng)
    P, B = entries.shape[0], entries.shape[1]
    extents = (rng.integers(1, B // ALIGN + 1, size=(P,)) * ALIGN).astype(
        np.int32
    )
    packed = np.asarray(_append_pallas(
        log, entries, base, do_write, extents=extents, interpret=True
    ))
    dense = np.asarray(append_rows_xla(log, entries, base, do_write))
    R = log.shape[0]
    for r in range(R):
        for p in range(P):
            b, rows = int(base[p]), _packed_rows_ref(extents[p], B)
            if do_write[r, p]:
                np.testing.assert_array_equal(
                    packed[r, p, b : b + rows], dense[r, p, b : b + rows]
                )
                np.testing.assert_array_equal(
                    packed[r, p, b + rows : b + B], log[r, p, b + rows : b + B]
                )
            else:
                np.testing.assert_array_equal(packed[r, p], log[r, p])


def test_packed_uniform_lockstep_block():
    """The hottest shape: every partition active, equal bases, one shared
    partial extent — the packed uniform fast path's single strided DMA
    must match the packed XLA fallback byte-for-byte."""
    rng = np.random.default_rng(11)
    R, P, S, SB, B = 3, 16, 64, 128, 16
    log = rng.integers(0, 256, size=(R, P, S, SB), dtype=np.uint8)
    entries = rng.integers(0, 256, size=(P, B, SB), dtype=np.uint8)
    base = np.full((P,), 2 * ALIGN, np.int32)
    do_write = np.ones((R, P), bool)
    extents = np.full((P,), ALIGN, np.int32)  # half the window
    got = np.asarray(_append_pallas(
        log, entries, base, do_write, extents=extents, interpret=True
    ))
    want = np.asarray(append_rows_xla(log, entries, base, do_write, extents))
    np.testing.assert_array_equal(got, want)
    # and the clipped region really was clipped: the tail rows of each
    # window keep their prior bytes.
    rows = _packed_rows_ref(ALIGN, B)
    assert rows < B
    b = 2 * ALIGN
    np.testing.assert_array_equal(
        got[:, :, b + rows : b + B], log[:, :, b + rows : b + B]
    )


def test_packed_mixed_extent_classes_demote_uniform_block():
    """Partitions of one grid block with DIFFERING extent classes must
    demote to the per-entry path and still match the fallback."""
    rng = np.random.default_rng(12)
    R, P, S, SB, B = 2, 16, 64, 128, 16
    log = rng.integers(0, 256, size=(R, P, S, SB), dtype=np.uint8)
    entries = rng.integers(0, 256, size=(P, B, SB), dtype=np.uint8)
    base = np.full((P,), ALIGN, np.int32)
    do_write = np.ones((R, P), bool)
    extents = np.full((P,), B, np.int32)
    extents[3] = ALIGN  # block 0 mixed classes; block 1 stays uniform
    got = np.asarray(_append_pallas(
        log, entries, base, do_write, extents=extents, interpret=True
    ))
    want = np.asarray(append_rows_xla(log, entries, base, do_write, extents))
    np.testing.assert_array_equal(got, want)


def test_packed_full_extent_equals_none():
    """extents == B everywhere must reproduce the extents=None
    full-window write exactly (the top class is the whole window)."""
    rng = np.random.default_rng(13)
    log, entries, base, do_write = rand_case(rng)
    P, B = entries.shape[0], entries.shape[1]
    extents = np.full((P,), B, np.int32)
    got = np.asarray(_append_pallas(
        log, entries, base, do_write, extents=extents, interpret=True
    ))
    want = np.asarray(append_rows_xla(log, entries, base, do_write))
    np.testing.assert_array_equal(got, want)


def test_packed_active_set_matches_dense():
    from ripplemq_tpu.ops.append import (
        _append_active_pallas,
        append_rows_active_xla,
    )

    rng = np.random.default_rng(14)
    log, entries, entries_c, ids, base, do_write = rand_sparse_case(rng)
    P, B = entries.shape[0], entries.shape[1]
    extents = (rng.integers(1, B // ALIGN + 1, size=(P,)) * ALIGN).astype(
        np.int32
    )
    got_xla = np.asarray(append_rows_active_xla(
        log.copy(), entries_c, ids, base, do_write, extents
    ))
    got_pl = np.asarray(_append_active_pallas(
        log.copy(), entries_c, ids, base, do_write, extents=extents,
        interpret=True,
    ))
    np.testing.assert_array_equal(got_pl, got_xla)


@pytest.mark.parametrize("spoiler", ["base", "active"])
def test_pallas_uniform_predicate_boundaries(spoiler):
    """One partition breaking the uniform predicate (a differing base,
    or an inactive slot) must demote ONLY its grid block to the
    per-entry path — neighbouring uniform blocks keep the fast path,
    and the result stays byte-identical either way."""
    rng = np.random.default_rng(8)
    R, P, S, SB, B = 2, 16, 64, 128, 16
    log = rng.integers(0, 256, size=(R, P, S, SB), dtype=np.uint8)
    entries = rng.integers(0, 256, size=(P, B, SB), dtype=np.uint8)
    base = np.full((P,), ALIGN, np.int32)
    do_write = np.ones((R, P), bool)
    if spoiler == "base":
        base[5] = 3 * ALIGN  # block 0 mixed; block 1 stays uniform
    else:
        do_write[1, 5] = False
    got = np.asarray(
        _append_pallas(log, entries, base, do_write, interpret=True)
    )
    want = np.asarray(append_rows_xla(log, entries, base, do_write))
    np.testing.assert_array_equal(got, want)


def test_no_quiet_scatter_on_a_tpu_backend():
    """The write phase is chosen once, at engine build: the kernel on a
    TPU, the scatter on the CPU test platform — and a row width the
    kernel cannot take is an ERROR on a TPU, never a silent scatter."""
    from ripplemq_tpu.core.config import EngineConfig
    from ripplemq_tpu.ops.append import append_backend
    from ripplemq_tpu.parallel.engine import make_local_fns

    assert append_backend(128, "tpu") == "pallas"
    assert append_backend(64, "cpu") == "xla"
    with pytest.raises(ValueError, match="multiple of 128"):
        append_backend(64, "tpu")
    # On this (CPU) process the binding reports what it compiled in.
    cfg = EngineConfig(partitions=4, replicas=3, slots=64, slot_bytes=64,
                       max_batch=8, read_batch=8)
    assert make_local_fns(cfg).append_backend == "xla"
