"""Log-append write phase: per-partition windowed DMA (Pallas TPU kernel).

The hot op of the whole system. Each committed round must write, for every
partition p that committed, a [B, SB] block of packed rows at that
partition's log end `base[p]` — a variable row offset per partition.

XLA offers two lowerings, both bad on TPU:
- vmapped `dynamic_update_slice`: P serialized windowed updates;
- batched row `scatter`: a row-serial scatter (R x P x B rows per
  round).

The Pallas kernel instead issues ONE async DMA per (replica, partition) —
a contiguous window of at most [B, SB], in place via input/output
aliasing, no copy of the untouched log. Mosaic requires window row
offsets aligned to the uint8 sublane tile, which the engine guarantees
by construction: log_end only ever advances in multiples of
core.config.ALIGN, and both arrays are viewed as
[..., S/ALIGN, ALIGN, SB] so the DMA offset lives in an untiled
dimension.

Semantics contract (shared with the XLA fallback, asserted in tests):
- whenever do_write[r, p], the written region is the partition's extent
  CLASS: power-of-two ALIGN-row blocks >= the ALIGN-rounded extent, up
  to the full B rows (see the extent-classes section below). Rows at
  index >= count carry length-0 headers (alignment padding) and the next
  committed round overwrites whatever padding trails its own base. Rows
  between the class and B keep their prior bytes — they are beyond the
  round's advance, so nothing below `commit` can ever read them.
  `extents=None` means every window is the full B rows. Both backends
  apply the identical class rule and stay bit-identical to each other;
- `base` is the PHYSICAL ring position (absolute log end mod cfg.slots;
  the engine wrappers compute it) — callers guarantee base[p] % ALIGN == 0
  and base[p] + B <= S_phys (the log array's row count, which is
  cfg.slots + the B-row wrap margin; see core.state) whenever
  do_write[r, p]. The control phase's trim-gated capacity rule keeps
  live rows out of the window's reclaimable tail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ripplemq_tpu.core.config import ALIGN


def append_backend(slot_bytes: int, platform: str | None = None) -> str:
    """Which write phase a program built for `platform` (default: this
    process's default backend) runs: "pallas" on a TPU, "xla" (the row
    scatter) anywhere else — CPU is the test platform, chosen from
    outside the program. On a TPU there is NO quiet scatter: a row
    width Mosaic cannot take (the lane dim must be 128-aligned) is an
    error here, at engine build (parallel.engine.make_local_fns /
    make_spmd_fns), not a row-serial write path nobody asked for."""
    if platform is None:
        platform = jax.default_backend()
    if platform != "tpu":
        return "xla"
    if slot_bytes % 128:
        raise ValueError(
            f"slot_bytes={slot_bytes} on a TPU backend: the append "
            f"kernel's DMA windows need a 128-aligned row width, and "
            f"the XLA row scatter is not a serving path on TPU — use a "
            f"multiple of 128"
        )
    return "pallas"


# Scoped VMEM a Pallas kernel gets by default, by the device's kind, as
# the installed compiler reads it (PR 33: the append kernel compiled for
# a described v5e:2x2, v5p:2x2x1, v4:2x2x1 and v6e:2x2 at SB 1152, A 32,
# B 904 / 912 / 1816 / 1824 / 3640 — "Scoped allocation with size 16.03M
# and limit 16.00M" at B 912 on the first three, 32.00M on v6e). A kind
# that is not listed is not priced at engine build: its compiler decides
# at warm-up.
SCOPED_VMEM_BYTES = {
    "TPU v4": 16 << 20,
    "TPU v5 lite": 16 << 20,
    "TPU v5": 16 << 20,
    "TPU v6 lite": 32 << 20,
}


def _pick_k(P: int, target: int = 8) -> int:
    k = min(target, P)
    while P % k:
        k -= 1
    return max(1, k)


def active_bucket(n: int, partitions: int) -> int:
    """Smallest active-set capacity bucket >= n (8, 32, 128, ... up to
    `partitions`): rounds compile once per bucket, not once per active
    count. The ladder lives here, beside the kernel whose shapes it
    picks; the batcher (DataPlane._active_bucket) and the VMEM price
    below read this one copy."""
    a = 8
    while a < n:
        a *= 4
    return max(1, min(a, partitions))


def active_buckets(partitions: int) -> tuple[int, ...]:
    """Every bucket a `partitions`-wide engine can hit, ascending:
    sweep n over doubling active counts up to P and collect the
    buckets they map to."""
    out = []
    n = 1
    while n < partitions:
        out.append(active_bucket(n, partitions))
        n *= 2
    out.append(active_bucket(partitions, partitions))
    return tuple(dict.fromkeys(out))


def check_entries_block(slot_bytes: int, max_batch: int, partitions: int,
                        device_kind: str) -> None:
    """Refuse, at engine build and with the numbers, a `max_batch` x
    `slot_bytes` whose `entries` block outgrows the scoped VMEM the
    kernel gets on `device_kind` — not a compiler refusal at warm-up.

    The kernel's one VMEM resident is its entries block, [Ka, B/ALIGN,
    ALIGN, SB] uint8 with Ka = _pick_k(A) partitions a grid step; the
    pipeline keeps two of them whenever bucket A spans more than one
    step, one otherwise. Priced over the buckets this shape can hit.
    The v5e compiler agrees to the row: at SB 1152, A 32 it takes B 904
    (2 x 8 x 904 x 1152 = 16,662,528 B) and refuses 912; at A 8, one
    step, it takes 1816 and refuses 1824 (PR 33;
    tests/test_wide_rows.py compiles the first pair)."""
    limit = SCOPED_VMEM_BYTES.get(device_kind)
    if limit is None:
        return
    def blocks(A):  # partitions' worth of rows the bucket keeps in VMEM
        ka = _pick_k(A)
        return (2 if A // ka > 1 else 1) * ka

    A = max(active_buckets(partitions), key=blocks)
    ka = _pick_k(A)
    need = blocks(A) * max_batch * slot_bytes
    if need > limit:
        fit = limit // (blocks(A) * slot_bytes) // ALIGN * ALIGN
        raise ValueError(
            f"max_batch={max_batch} x slot_bytes={slot_bytes} on "
            f"{device_kind}: at the {A}-partition bucket the append "
            f"kernel's entries block is {blocks(A) // ka} x {ka} x "
            f"{max_batch} x {slot_bytes} = {need} B, over the {limit} B "
            f"of scoped VMEM a kernel gets — at this row width and "
            f"{partitions} partitions max_batch can be at most {fit}"
        )


# --------------------------------------------------------- extent classes
#
# Length-aware writes: instead of always moving the full [B, SB] window,
# the copy is clipped to the round's payload extent. Pallas DMAs need
# static shapes, so the dynamic extent is rounded UP to a power-of-two
# class of ALIGN-row blocks — one predicated DMA of the matching class
# fires per window (one issue per window whatever its class; at most 2x
# the true extent in bytes, proportionally fewer HBM bytes for small
# rounds). The XLA fallback applies the SAME class rule so both backends
# stay bit-identical.


def _extent_classes(BA: int) -> list[int]:
    """Ascending copy-size classes in ALIGN-row blocks: powers of two
    plus the full window (BA itself, whether or not it is a power)."""
    sizes = set()
    s = 1
    while s < BA:
        sizes.add(s)
        s *= 2
    sizes.add(BA)
    return sorted(sizes)


def _class_roundup(eb, BA: int):
    """Smallest class >= eb (works on scalars and vectors; eb is in
    ALIGN-row blocks, already clipped to [0, BA])."""
    classes = _extent_classes(BA)
    pb = jnp.full_like(eb, classes[-1])
    for s in reversed(classes):
        pb = jnp.where(eb <= jnp.int32(s), jnp.int32(s), pb)
    return pb


def class_rows(extents, B: int):
    """Host (numpy) form of the class rule: the rows a write of
    `extents` rows moves - what the batcher's staging has to fill and
    stamp (DataPlane._stage). Held to `_class_roundup` by
    tests/test_staging.py."""
    BA = B // ALIGN
    classes = np.asarray(_extent_classes(BA))
    eb = np.clip((np.asarray(extents) + ALIGN - 1) // ALIGN, 1, BA)
    return classes[np.searchsorted(classes, eb)] * ALIGN


def _extent_blocks(extents, P: int, B: int):
    """Host row extents [P] -> ALIGN-row block counts [P], clipped.
    None (a caller that names no extent) is the full window: the top
    class, B // ALIGN blocks, for every partition."""
    if extents is None:
        return jnp.full((P,), B // ALIGN, jnp.int32)
    return (jnp.clip(extents.astype(jnp.int32), 0, B) + ALIGN - 1) // ALIGN


def _append_pallas(log_data, entries, base, do_write, *, extents=None,
                   interpret=False):
    """Dense write = the active-set kernel with every partition listed
    (ids = arange(P)); one kernel to maintain."""
    P = log_data.shape[1]
    return _append_active_pallas(
        log_data, entries, jnp.arange(P, dtype=jnp.int32), base, do_write,
        extents=extents, interpret=interpret,
    )


def append_rows_xla(log_data, entries, base, do_write, extents=None):
    """XLA fallback (row scatter) with identical semantics.

    Handles both the per-replica shape ([P, S, SB] log with [P] do_write —
    the `replica_step` composition under vmap) and the full-cluster shape
    ([R, P, S, SB] log with [R, P] do_write). Dense = the active-set
    scatter over every partition."""
    P = log_data.shape[-3]
    return append_rows_active_xla(
        log_data, entries, jnp.arange(P, dtype=jnp.int32), base, do_write,
        extents,
    )


def _kernel_active(Ka: int, BA: int, ids_ref, base_ref, dw_ref, eb_ref,
                   entries_ref, log_in, log_out, sems):
    """One grid step: the Ka active-set entries of block c, for replica
    r. Each listed partition that writes gets ONE DMA start, its copy
    region clipped to the partition's extent class (see the
    extent-classes section above); the class predicates are scalar-core
    compares."""
    r = pl.program_id(0)
    c = pl.program_id(1)
    classes = _extent_classes(BA)

    def pblocks(p):
        return _class_roundup(jnp.clip(eb_ref[p], 1, BA), BA)

    def active(a):
        # Padding entries carry id -1; `&` evaluates both operands, so
        # the do_write gather must use a clamped index.
        p = jnp.maximum(ids_ref[a], 0)
        return (ids_ref[a] >= 0) & (dw_ref[r, p] != 0)

    def copy(k, a, s):
        p = jnp.maximum(ids_ref[a], 0)
        b = base_ref[p] // ALIGN
        return pltpu.make_async_copy(
            entries_ref.at[k, pl.ds(0, s)],
            log_out.at[r, p, pl.ds(b, s), :, :],
            sems.at[k],
        )

    # UNIFORM fast path: when this block's Ka partitions are CONSECUTIVE,
    # all active, and share one base and one extent class (bulk uniform
    # ingest — every partition of a dense round advancing in lockstep),
    # the Ka windows form one strided region and ONE DMA covers them
    # all. The write phase is DMA-ISSUE-bound (~0.8 µs of scalar-core
    # work per start; R x A issues per round), so collapsing Ka issues
    # into one is a direct multiplier on uniform traffic; mixed traffic
    # takes the per-entry path below.
    p0 = ids_ref[c * Ka]
    b0 = base_ref[jnp.maximum(p0, 0)] // ALIGN
    pb0 = pblocks(jnp.maximum(p0, 0))
    uniform = jnp.bool_(Ka > 1)
    for k in range(Ka):
        a = c * Ka + k
        pk = ids_ref[a]
        pkc = jnp.maximum(pk, 0)
        uniform &= (pk == p0 + k) & active(a)
        uniform &= base_ref[pkc] // ALIGN == b0
        uniform &= pblocks(pkc) == pb0

    for s in classes:

        @pl.when(uniform & (pb0 == s))
        def _(s=s):
            cp = pltpu.make_async_copy(
                entries_ref.at[:, pl.ds(0, s)],
                log_out.at[r, pl.ds(p0, Ka), pl.ds(b0, s), :, :],
                sems.at[0],
            )
            cp.start()
            cp.wait()

    @pl.when(~uniform)
    def _():
        for k in range(Ka):  # static unroll; Ka and the class set are small
            a = c * Ka + k
            for s in classes:

                @pl.when(active(a) & (pblocks(jnp.maximum(ids_ref[a], 0)) == s))
                def _(k=k, a=a, s=s):
                    copy(k, a, s).start()

        for k in range(Ka):
            a = c * Ka + k
            for s in classes:

                @pl.when(active(a) & (pblocks(jnp.maximum(ids_ref[a], 0)) == s))
                def _(k=k, a=a, s=s):
                    copy(k, a, s).wait()


def _append_active_pallas(log_data, entries, slot_ids, base, do_write, *,
                          extents=None, interpret=False):
    R, P, S, SB = log_data.shape
    A, B = entries.shape[0], entries.shape[1]
    BA = B // ALIGN
    Ka = _pick_k(A)
    log_v = log_data.reshape(R, P, S // ALIGN, ALIGN, SB)
    entries_v = entries.reshape(A, BA, ALIGN, SB)
    ids = jnp.where(slot_ids >= 0, jnp.clip(slot_ids, 0, P - 1), -1)
    scalars = (ids, base, do_write.astype(jnp.int32),
               _extent_blocks(extents, P, B))
    n_scalar = len(scalars)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalar,  # ids, base, do_write, ext blocks
        grid=(R, A // Ka),
        in_specs=[
            pl.BlockSpec((Ka, BA, ALIGN, SB), lambda r, c, *_: (c, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((Ka,))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_active, Ka, BA),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(log_v.shape, log_v.dtype),
        # input index = scalar-prefetch args, then entries, then log.
        input_output_aliases={n_scalar + 1: 0},
        interpret=interpret,
    )(*scalars, entries_v, log_v)
    return out.reshape(R, P, S, SB)


def append_rows_active_xla(log_data, entries, slot_ids, base, do_write,
                           extents=None):
    """XLA fallback for the active-set write: scatter entries[a]'s rows
    into partition slot_ids[a] (per replica). `extents` clips each
    window to the partition's extent class — the same rule as the
    Pallas kernel, so the two stay bit-identical."""
    if log_data.ndim == 4:
        return jax.vmap(append_rows_active_xla,
                        in_axes=(0, None, None, None, 0, None))(
            log_data, entries, slot_ids, base, do_write, extents
        )
    P, S, SB = log_data.shape
    A, B = entries.shape[0], entries.shape[1]
    ids = jnp.clip(slot_ids, 0, P - 1)
    write = (slot_ids >= 0) & jnp.take(do_write, ids)          # [A]
    rows = jnp.arange(B, dtype=jnp.int32)[None, :]             # [1, B]
    eb = jnp.clip(_extent_blocks(extents, P, B), 1, B // ALIGN)
    rows_lim = _class_roundup(eb, B // ALIGN) * ALIGN          # [P]
    in_window = write[:, None] & (rows < jnp.take(rows_lim, ids)[:, None])
    ridx = jnp.where(in_window, jnp.take(base, ids)[:, None] + rows, S)
    pidx = jnp.broadcast_to(ids[:, None], (A, B))
    return log_data.at[pidx, ridx].set(entries, mode="drop")


def append_rows_active(log_data, entries, slot_ids, base, do_write, *,
                       extents=None,
                       use_pallas: bool | None = None,
                       interpret: bool = False):
    """Active-set write phase: entries [A, B, SB] carry only the A
    partitions that have appends this round; slot_ids [A] maps each
    block to its partition (-1 = padding). Identical semantics to
    append_rows restricted to the listed partitions — the input
    compaction is the point: a sparse round ships A x B x SB bytes
    instead of P x B x SB (16-128x smaller under realistic fan-out),
    and input transfer rides every dispatch.

    Same contracts as append_rows (`base` physical, ALIGN-aligned;
    extent-class windows, full-B where `extents` is None; do_write
    [R, P]); additionally each partition appears at most once
    in slot_ids per round."""
    if use_pallas is None:
        use_pallas = append_backend(log_data.shape[-1]) == "pallas"
    if use_pallas or interpret:
        return _append_active_pallas(log_data, entries, slot_ids, base,
                                     do_write, extents=extents,
                                     interpret=interpret)
    return append_rows_active_xla(log_data, entries, slot_ids, base,
                                  do_write, extents)


def append_rows(log_data, entries, base, do_write, *, extents=None,
                use_pallas: bool | None = None,
                interpret: bool = False):
    """Dispatch: Pallas kernel on TPU, XLA scatter elsewhere
    (append_backend; the engine bindings decide once at build and pass
    `use_pallas` explicitly).

    Inputs: log_data [R, P, S, SB] (donated/aliased in place on the pallas
    path), entries [P, B, SB] packed rows, base [P] (leader log end,
    replica-invariant, ALIGN-aligned), do_write [R, P] bool, extents [P]
    rows (each window is clipped to the partition's extent class; None =
    full windows).
    """
    if use_pallas is None:
        use_pallas = append_backend(log_data.shape[-1]) == "pallas"
    if use_pallas or interpret:
        return _append_pallas(log_data, entries, base, do_write,
                              extents=extents, interpret=interpret)
    return append_rows_xla(log_data, entries, base, do_write, extents)
