"""Compiled engine entry points: local (vmap) and SPMD (shard_map) modes.

The core steps in `ripplemq_tpu.core.step` are written once against the
axis name "replica". This module binds them two ways:

- **local**: `jax.vmap(..., axis_name="replica")` stacks all replicas on a
  leading axis of a single device's arrays. Used for single-chip
  deployments and deterministic tests — the replication round is then a
  pure function: same tensors in → same commit index out (SURVEY.md §4).

- **spmd**: `shard_map` over a (replica, part) `Mesh` — one device per
  replica × partition-shard; psums ride ICI/DCN. This is the multi-chip
  production path.

Both produce bit-identical semantics (asserted in tests/test_spmd.py).
"""

from __future__ import annotations

import functools
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ripplemq_tpu.core.config import EngineConfig, stride_alias_hazard
from ripplemq_tpu.core.state import (
    FusedReplicaState,
    ReplicaState,
    StepInput,
    StepOutput,
    fuse_state,
    init_state,
    unfuse_state,
)
from ripplemq_tpu.core import step as core_step
from ripplemq_tpu.ops.append import (
    append_backend,
    check_entries_block,
    append_rows,
    append_rows_active,
)
from ripplemq_tpu.utils.program_store import ProgramStore, default_directory


class LocalEngineFns(NamedTuple):
    init: Callable[[], FusedReplicaState]     # -> state with leading [R] axis
    step: Callable[..., tuple[FusedReplicaState, StepOutput]]
    step_many: Callable[..., tuple[FusedReplicaState, StepOutput]]  # chained rounds
    step_sparse: Callable[..., tuple[FusedReplicaState, StepOutput]]  # active-set
    step_many_sparse: Callable[..., tuple[FusedReplicaState, StepOutput]]
    vote: Callable[..., tuple[FusedReplicaState, jax.Array, jax.Array]]
    read: Callable[..., tuple[jax.Array, jax.Array, jax.Array]]
    read_many: Callable[..., tuple[jax.Array, jax.Array, jax.Array]]  # batched
    read_offset: Callable[..., jax.Array]
    resync: Callable[..., FusedReplicaState]
    init_from: Callable[[ReplicaState], FusedReplicaState]  # single-replica image -> [R] state
    append_backend: str  # "pallas" | "xla" — the write phase compiled in


class SpmdEngineFns(NamedTuple):
    init: Callable[[], FusedReplicaState]
    step: Callable[..., tuple[FusedReplicaState, StepOutput]]
    step_many: Callable[..., tuple[FusedReplicaState, StepOutput]]
    step_sparse: Callable[..., tuple[FusedReplicaState, StepOutput]]
    step_many_sparse: Callable[..., tuple[FusedReplicaState, StepOutput]]
    vote: Callable[..., tuple[FusedReplicaState, jax.Array, jax.Array]]
    read: Callable[..., tuple[jax.Array, jax.Array, jax.Array]]
    read_many: Callable[..., tuple[jax.Array, jax.Array, jax.Array]]
    read_offset: Callable[..., jax.Array]
    resync: Callable[..., FusedReplicaState]
    init_from: Callable[[ReplicaState], FusedReplicaState]
    append_backend: str
    mesh: Mesh


# ---------------------------------------------------------------------------
# Resync (shared): copy one healthy replica's rows into a recovering replica.
# ---------------------------------------------------------------------------

def _resync(cfg: EngineConfig, state: ReplicaState, src: jax.Array,
            dst: jax.Array, part_mask: jax.Array) -> ReplicaState:
    """Overwrite replica `dst`'s state for masked partitions with replica
    `src`'s (the leader's) state. State here carries an explicit leading
    replica axis [R, ...]. This is the snapshot-install analogue: the
    reference inherits full log replay from JRaft and has no FSM snapshots
    (SURVEY.md §5 checkpoint); here recovery is one on-device copy.
    """
    R = cfg.replicas

    def copy_leaf(leaf):
        src_rows = leaf[src]                       # [P, ...]
        mask = part_mask.reshape((1, -1) + (1,) * (leaf.ndim - 2))
        is_dst = (jnp.arange(R) == dst).reshape((R,) + (1,) * (leaf.ndim - 1))
        return jnp.where(is_dst & mask, src_rows[None], leaf)

    return jax.tree.map(copy_leaf, state)


# ---------------------------------------------------------------------------
# Local (single device, replicas vmapped)
# ---------------------------------------------------------------------------

def make_local_fns(cfg: EngineConfig,
                   programs: ProgramStore | None = None) -> LocalEngineFns:
    """`programs` is where the round programs a boot warms (the sparse
    single and chained rounds, the vote) are loaded from and written to
    (utils/program_store.py); left out, the store of the compile
    cache's directory - none in a process pinned to the CPU backend,
    which then runs plain `jit` throughout."""
    R = cfg.replicas
    if programs is None:
        programs = ProgramStore(default_directory())
    # The write phase is chosen ONCE, here (ops.append.append_backend):
    # the Pallas kernel on a TPU — a slot_bytes Mosaic cannot take, or a
    # max_batch x slot_bytes block over the kernel's VMEM, raises —
    # and the XLA scatter on the CPU test platform.
    backend = append_backend(cfg.slot_bytes)
    pallas = backend == "pallas"
    if pallas:
        check_entries_block(cfg.slot_bytes, cfg.max_batch, cfg.partitions,
                            jax.devices()[0].device_kind)
    rep_idx = jnp.arange(R, dtype=jnp.int32)
    default_quorum = jnp.full((cfg.partitions,), cfg.quorum, jnp.int32)

    # The state is the stacked layout (core.state.FusedReplicaState): the
    # bookkeeping scalars ride one [R, K, P] ctrl array; the read paths
    # go through its named accessors.
    @jax.jit
    def _init():
        one = fuse_state(init_state(cfg))
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape).copy(), one)

    vctrl = jax.vmap(
        functools.partial(core_step.replica_control, cfg),
        in_axes=(0, None, 0, None, None, None),
        axis_name=core_step.AXIS,
    )
    default_trim = jnp.zeros((cfg.partitions,), jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step_j(state, inp: StepInput, alive, quorum, trim):
        # Control phase per replica (vmapped), then ONE batched write phase
        # on the full [R, P, S+B, SB] ring (Pallas DMA kernel on TPU; the
        # window lands at the physical ring position base % slots and
        # covers the round's extent, replica-invariant like base).
        new_state, ctl = vctrl(state, inp, rep_idx, alive, quorum, trim)
        log_data = append_rows(
            state.log_data, inp.entries, ctl.out.base[0] % cfg.slots,
            ctl.do_write, extents=ctl.extent[0], use_pallas=pallas
        )
        new_state = new_state._replace(log_data=log_data)
        # outputs are replica-invariant after the psum; take replica 0's copy
        return new_state, jax.tree.map(lambda x: x[0], ctl.out)

    def _step(state, inp, alive, quorum=None, trim=None):
        return _step_j(state, inp, alive,
                       default_quorum if quorum is None else quorum,
                       default_trim if trim is None else trim)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step_many_j(state, inputs: StepInput, alive, quorum, trim):
        # K chained rounds in ONE dispatch: `inputs` leaves carry a
        # leading chain axis [K, ...]. The fixed per-launch cost
        # (dispatch, and the resolver's host fetch of the result)
        # amortizes over the chain; each scan iteration is a COMPLETE
        # quorum round — ballot before write, atomic, commit advanced —
        # so chaining changes throughput, not semantics. alive/quorum/
        # trim are chain-constant, which gives the per-slot committed-
        # prefix property the host batcher relies on (broker.dataplane
        # burst drain): once a slot's round fails (quorum/capacity under
        # fixed conditions), every later round of the chain fails too.
        def body(st, inp):
            new_st, ctl = vctrl(st, inp, rep_idx, alive, quorum, trim)
            log = append_rows(
                st.log_data, inp.entries, ctl.out.base[0] % cfg.slots,
                ctl.do_write, extents=ctl.extent[0], use_pallas=pallas
            )
            return (
                new_st._replace(log_data=log),
                jax.tree.map(lambda x: x[0], ctl.out),
            )

        return jax.lax.scan(body, state, inputs)

    def _step_many(state, inputs, alive, quorum=None, trim=None):
        return _step_many_j(state, inputs, alive,
                            default_quorum if quorum is None else quorum,
                            default_trim if trim is None else trim)

    # Active-set (sparse) variants: `inp.entries` is a tiny dummy (the
    # control phase never reads it); the real rows arrive compacted as
    # entries_c [A, B, SB] + slot_ids [A] (-1 pads) and land via the
    # active-set write kernel. A sparse round ships A/P of the dense
    # input bytes — and input transfer rides every dispatch (the broker
    # batcher uses these; see ops.append.append_rows_active).
    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step_sparse_j(state, inp, entries_c, slot_ids, alive, quorum, trim):
        new_state, ctl = vctrl(state, inp, rep_idx, alive, quorum, trim)
        log_data = append_rows_active(
            state.log_data, entries_c, slot_ids,
            ctl.out.base[0] % cfg.slots, ctl.do_write, extents=ctl.extent[0],
            use_pallas=pallas,
        )
        new_state = new_state._replace(log_data=log_data)
        return new_state, jax.tree.map(lambda x: x[0], ctl.out)

    # entries_c is [A, B, SB], chained [K, A, B, SB]: A is the bucket.
    def _bucket(state, inp, entries_c, *rest):
        return f"bucket {entries_c.shape[-3]}"

    _step_sparse_j = programs.wrap(_step_sparse_j, cfg, backend, _bucket)

    def _step_sparse(state, inp, entries_c, slot_ids, alive, quorum=None,
                     trim=None):
        return _step_sparse_j(state, inp, entries_c, slot_ids, alive,
                              default_quorum if quorum is None else quorum,
                              default_trim if trim is None else trim)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step_many_sparse_j(state, inputs, entries_c, slot_ids, alive,
                            quorum, trim):
        def body(st, per_round):
            inp, ec, ids = per_round
            new_st, ctl = vctrl(st, inp, rep_idx, alive, quorum, trim)
            log = append_rows_active(
                st.log_data, ec, ids, ctl.out.base[0] % cfg.slots,
                ctl.do_write, extents=ctl.extent[0], use_pallas=pallas
            )
            return (
                new_st._replace(log_data=log),
                jax.tree.map(lambda x: x[0], ctl.out),
            )

        return jax.lax.scan(body, state, (inputs, entries_c, slot_ids))

    _step_many_sparse_j = programs.wrap(_step_many_sparse_j, cfg, backend,
                                        _bucket)

    def _step_many_sparse(state, inputs, entries_c, slot_ids, alive,
                          quorum=None, trim=None):
        return _step_many_sparse_j(
            state, inputs, entries_c, slot_ids, alive,
            default_quorum if quorum is None else quorum,
            default_trim if trim is None else trim)

    vvote = jax.vmap(
        functools.partial(core_step.vote_step, cfg),
        in_axes=(0, None, None, 0, None, None),
        axis_name=core_step.AXIS,
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _vote_j(state, cand, cand_term, alive, quorum):
        new_state, elected, votes = vvote(state, cand, cand_term, rep_idx,
                                          alive, quorum)
        return new_state, elected[0], votes[0]

    _vote_j = programs.wrap(_vote_j, cfg, backend)

    def _vote(state, cand, cand_term, alive, quorum=None):
        return _vote_j(state, cand, cand_term, alive,
                       default_quorum if quorum is None else quorum)

    @jax.jit
    def _read(state, replica, partition, offset):
        replica = jnp.clip(replica, 0, R - 1)
        one = jax.tree.map(lambda x: x[replica], state)
        return core_step.read_batch(cfg, one, partition, offset)

    @jax.jit
    def _read_many(state, replicas, partitions, offsets):
        # Batched committed reads: Q independent (replica, partition,
        # offset) queries in ONE dispatch — the consume-side mirror of
        # append batching (each read dispatch costs a full host<->device
        # round trip, which dominates when many consumers poll). Queries
        # address the full log via read_batch_at: each moves only its
        # own window, never a whole-replica slice.
        def one(rep, part, off):
            return core_step.read_batch_at(
                cfg, state.log_data, state.commit, rep, part, off
            )

        return jax.vmap(one)(replicas, partitions, offsets)

    @jax.jit
    def _read_offset(state, replica, partition, consumer_slot):
        replica = jnp.clip(replica, 0, R - 1)
        one = jax.tree.map(lambda x: x[replica], state)
        return core_step.read_offset(one, partition, consumer_slot)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _resync_fn(state, src, dst, part_mask):
        # _resync's masking assumes [R, P, ...] leaves; the ctrl leaf is
        # [R, K, P]. Resync is the rare recovery path, so round-trip
        # through the named layout instead of teaching the masking about
        # the stacked axis.
        return fuse_state(
            _resync(cfg, unfuse_state(state), src, dst, part_mask)
        )

    def _init_from(image: ReplicaState):
        """Install a recovered single-replica image on every replica slot
        (all replicas are identical post-commit — only committed rounds
        are ever persisted)."""
        import numpy as np
        full = jax.tree.map(
            lambda x: jnp.asarray(np.broadcast_to(np.asarray(x), (R,) + np.asarray(x).shape)),
            image,
        )
        return fuse_state(full)

    return LocalEngineFns(_init, _step, _step_many, _step_sparse,
                          _step_many_sparse, _vote, _read, _read_many,
                          _read_offset, _resync_fn, _init_from, backend)


# ---------------------------------------------------------------------------
# SPMD (mesh: replica × part)
# ---------------------------------------------------------------------------

def _state_specs() -> FusedReplicaState:
    """PartitionSpecs for the full-cluster state: the log and the offset
    table are [R, P, ...] (replica axis over "replica", partition axis
    over "part"); the stacked ctrl buffer is [R, K, P] — replica axis
    sharded, the K bookkeeping rows whole WITHIN a device, partition
    axis over "part". Each device then holds its shard's whole
    [K, local_P] bookkeeping block, so a round's four scalar advances
    stay ONE wide select on one local buffer and the two leader
    broadcasts ride ONE [2, local_P] psum over the replica mesh axis."""
    return FusedReplicaState(
        log_data=P("replica", "part", None, None),
        ctrl=P("replica", None, "part"),
        offsets=P("replica", "part", None),
    )


def _input_specs() -> StepInput:
    """Inputs carry no replica axis: XLA's data distribution replicates
    them over the replica mesh axis (this IS the AppendEntries fan-out).
    extents is always present here: None extents are pytree-empty and
    would be a treedef mismatch against the compiled specs, so the spmd
    wrappers fill missing extents with the full window first
    (_fill_extents)."""
    return StepInput(
        entries=P("part", None, None),
        counts=P("part"),
        off_slots=P("part", None),
        off_vals=P("part", None),
        off_counts=P("part"),
        leader=P("part"),
        term=P("part"),
        extents=P("part"),
    )


def _smap(f, mesh, in_specs, out_specs):
    """shard_map with the varying-manual-axes checker off: the Pallas
    write kernel's out_shape carries no vma annotation, which JAX
    rejects under check_vma inside shard_map on TPU. The checker is a
    static lint, not a semantics change; the engine's replication
    invariants are asserted dynamically by tests/test_spmd.py."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_spmd_fns(cfg: EngineConfig, mesh: Mesh) -> SpmdEngineFns:
    R = cfg.replicas
    part_shards = mesh.shape["part"]
    if mesh.shape["replica"] != R:
        raise ValueError(
            f"mesh replica axis {mesh.shape['replica']} != cfg.replicas {R}"
        )
    if cfg.partitions % part_shards:
        raise ValueError("partitions must divide evenly over the part axis")
    local_P = cfg.partitions // part_shards
    # Same one-time write-phase choice as the local binding, priced at
    # the platform of the devices this mesh actually spans.
    backend = append_backend(cfg.slot_bytes,
                             mesh.devices.flat[0].platform)
    pallas = backend == "pallas"
    if pallas:
        check_entries_block(cfg.slot_bytes, cfg.max_batch, cfg.partitions,
                            mesh.devices.flat[0].device_kind)

    # The ring-stride aliasing rule priced at the PER-DEVICE shape: each
    # mesh device holds ONE replica's [local_P, S+B, SB] ring block, so
    # local_P is the concurrent strided-DMA stream count — the global-P
    # verdict EngineConfig warns with at construction can be wrong in
    # both directions for a sharded deployment (core.config).
    hazard = stride_alias_hazard(cfg.slots, cfg.max_batch, cfg.slot_bytes,
                                 streams=local_P)
    if hazard is not None:
        warnings.warn(
            f"spmd binding: per-device shard holds {local_P} partition "
            f"rings; {hazard}", UserWarning, stacklevel=2,
        )

    st_specs = _state_specs()
    in_specs = _input_specs()
    rep_ids = jnp.arange(R, dtype=jnp.int32)

    def _squeeze(tree):
        return jax.tree.map(lambda x: x[0], tree)

    def _expand(tree):
        return jax.tree.map(lambda x: x[None], tree)

    def _norm_alive(alive):
        """Engine-level liveness is always [P, R] (per-partition replica
        masks; see core.step._normalize_alive); a [R] mask is broadcast."""
        alive = jnp.asarray(alive)
        if alive.ndim == 1:
            alive = jnp.broadcast_to(alive[None, :], (cfg.partitions, R))
        return alive

    default_quorum = jnp.full((cfg.partitions,), cfg.quorum, jnp.int32)

    default_trim = jnp.zeros((cfg.partitions,), jnp.int32)

    def _fill_extents(inp: StepInput) -> StepInput:
        """Hand-built inputs may leave extents=None (pytree-empty); the
        compiled specs carry a per-part extents shard, so fill with the
        full window (what core.step._write_extent makes of None). Chained
        inputs carry the leading chain axis on every leaf, counts
        included."""
        if inp.extents is not None:
            return inp
        return inp._replace(
            extents=jnp.full(inp.counts.shape, cfg.max_batch, jnp.int32)
        )

    def _gather_part(tree):
        """Replicate per-shard [P_local] outputs to full [P] on every
        device. Outputs are tiny int32/bool vectors, and full replication
        lets the host fetch them with a plain np.asarray even when the
        mesh spans processes (multi-host: every process holds an
        addressable copy). Built as a masked psum — the same pattern as
        the read path — so shard_map's replication checker knows the
        result is invariant over "part"."""
        idx = jax.lax.axis_index("part")

        def g(x):
            v = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x
            full = jnp.zeros((part_shards,) + v.shape, v.dtype)
            full = jax.lax.dynamic_update_index_in_dim(full, v, idx, 0)
            out = jax.lax.psum(full, "part").reshape(
                (part_shards * v.shape[0],) + v.shape[1:]
            )
            return out.astype(jnp.bool_) if x.dtype == jnp.bool_ else out

        return jax.tree.map(g, tree)

    # ---- step -------------------------------------------------------------
    def step_body(state, inp, rep, alive, quorum, trim):
        st = _squeeze(state)          # strip the size-1 replica block dim
        new_st, ctl = core_step.replica_control(
            cfg, st, inp, rep[0], alive, quorum, trim
        )
        # Write phase on this device's [1, P_local, S+B, SB] ring block.
        log_data = append_rows(
            st.log_data[None], inp.entries, ctl.out.base % cfg.slots,
            ctl.do_write[None], extents=ctl.extent, use_pallas=pallas,
        )
        new_st = new_st._replace(log_data=log_data[0])
        # out is psum-replicated over "replica"; gather it over "part".
        return _expand(new_st), _gather_part(ctl.out)

    smapped_step = _smap(
        step_body,
        mesh,
        in_specs=(st_specs, in_specs, P("replica"), P("part", None), P("part"),
                  P("part")),
        out_specs=(st_specs, StepOutput(P(), P(), P(), P())),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step_j(state, inp, alive, quorum, trim):
        return smapped_step(state, inp, rep_ids, _norm_alive(alive), quorum,
                            trim)

    def _step(state, inp, alive, quorum=None, trim=None):
        return _step_j(state, _fill_extents(inp), alive,
                       default_quorum if quorum is None else quorum,
                       default_trim if trim is None else trim)

    # Chained rounds (see the local binding's _step_many_j for the
    # rationale): scan INSIDE shard_map, so one dispatch commits K
    # complete quorum rounds with all collectives on the mesh.
    def step_many_body(state, inputs, rep, alive, quorum, trim):
        def body(st_block, inp):
            new_st, out = step_body(st_block, inp, rep, alive, quorum, trim)
            return new_st, out

        return jax.lax.scan(body, state, inputs)

    in_specs_k = jax.tree.map(
        lambda s: P(*((None,) + tuple(s))), in_specs,
        is_leaf=lambda s: isinstance(s, P),
    )
    smapped_step_many = _smap(
        step_many_body,
        mesh,
        in_specs=(st_specs, in_specs_k, P("replica"), P("part", None),
                  P("part"), P("part")),
        out_specs=(st_specs, StepOutput(P(), P(), P(), P())),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step_many_j(state, inputs, alive, quorum, trim):
        return smapped_step_many(state, inputs, rep_ids, _norm_alive(alive),
                                 quorum, trim)

    def _step_many(state, inputs, alive, quorum=None, trim=None):
        return _step_many_j(state, _fill_extents(inputs), alive,
                            default_quorum if quorum is None else quorum,
                            default_trim if trim is None else trim)

    # ---- sparse (active-set) steps ---------------------------------------
    # entries_c/slot_ids are replicated to every shard; each shard maps
    # the GLOBAL ids into its partition range (-1 = not mine/padding) and
    # writes only its own blocks.
    def _local_ids(ids):
        my_shard = jax.lax.axis_index("part")
        lo = my_shard * local_P
        mine = (ids >= lo) & (ids < lo + local_P)
        return jnp.where(mine, ids - lo, -1)

    def step_sparse_body(state, inp, entries_c, slot_ids, rep, alive,
                         quorum, trim):
        st = _squeeze(state)
        new_st, ctl = core_step.replica_control(
            cfg, st, inp, rep[0], alive, quorum, trim
        )
        log_data = append_rows_active(
            st.log_data[None], entries_c, _local_ids(slot_ids),
            ctl.out.base % cfg.slots, ctl.do_write[None],
            extents=ctl.extent, use_pallas=pallas,
        )
        new_st = new_st._replace(log_data=log_data[0])
        return _expand(new_st), _gather_part(ctl.out)

    smapped_step_sparse = _smap(
        step_sparse_body,
        mesh,
        in_specs=(st_specs, in_specs, P(None, None, None), P(None),
                  P("replica"), P("part", None), P("part"), P("part")),
        out_specs=(st_specs, StepOutput(P(), P(), P(), P())),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step_sparse_j(state, inp, entries_c, slot_ids, alive, quorum, trim):
        return smapped_step_sparse(state, inp, entries_c, slot_ids, rep_ids,
                                   _norm_alive(alive), quorum, trim)

    def _step_sparse(state, inp, entries_c, slot_ids, alive, quorum=None,
                     trim=None):
        return _step_sparse_j(state, _fill_extents(inp), entries_c, slot_ids,
                              alive,
                              default_quorum if quorum is None else quorum,
                              default_trim if trim is None else trim)

    def step_many_sparse_body(state, inputs, entries_c, slot_ids, rep,
                              alive, quorum, trim):
        def body(st_block, per_round):
            inp, ec, ids = per_round
            return step_sparse_body(st_block, inp, ec, ids, rep, alive,
                                    quorum, trim)

        return jax.lax.scan(body, state, (inputs, entries_c, slot_ids))

    smapped_step_many_sparse = _smap(
        step_many_sparse_body,
        mesh,
        in_specs=(st_specs, in_specs_k, P(None, None, None, None),
                  P(None, None), P("replica"), P("part", None), P("part"),
                  P("part")),
        out_specs=(st_specs, StepOutput(P(), P(), P(), P())),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _step_many_sparse_j(state, inputs, entries_c, slot_ids, alive,
                            quorum, trim):
        return smapped_step_many_sparse(
            state, inputs, entries_c, slot_ids, rep_ids,
            _norm_alive(alive), quorum, trim)

    def _step_many_sparse(state, inputs, entries_c, slot_ids, alive,
                          quorum=None, trim=None):
        return _step_many_sparse_j(
            state, _fill_extents(inputs), entries_c, slot_ids, alive,
            default_quorum if quorum is None else quorum,
            default_trim if trim is None else trim)

    # ---- vote -------------------------------------------------------------
    def vote_body(state, cand, cand_term, rep, alive, quorum):
        st = _squeeze(state)
        new_st, elected, votes = core_step.vote_step(
            cfg, st, cand, cand_term, rep[0], alive, quorum
        )
        elected, votes = _gather_part((elected, votes))
        return _expand(new_st), elected, votes

    smapped_vote = _smap(
        vote_body,
        mesh,
        in_specs=(st_specs, P("part"), P("part"), P("replica"),
                  P("part", None), P("part")),
        out_specs=(st_specs, P(), P()),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _vote_j(state, cand, cand_term, alive, quorum):
        return smapped_vote(state, cand, cand_term, rep_ids,
                            _norm_alive(alive), quorum)

    def _vote(state, cand, cand_term, alive, quorum=None):
        return _vote_j(state, cand, cand_term, alive,
                       default_quorum if quorum is None else quorum)

    # ---- read (broadcast the serving replica's window to every device) ----
    def read_body(state, rep, replica, partition, offset):
        st = _squeeze(state)
        my_rep = rep[0]
        # global partition -> (shard, local index); shards are contiguous
        shard = partition // local_P
        local_idx = partition % local_P
        my_shard = jax.lax.axis_index("part")
        data, lens, count = core_step.read_batch(cfg, st, local_idx, offset)
        sel = (my_rep == replica) & (my_shard == shard)
        zero = jnp.int32(0)
        data = jax.lax.psum(jnp.where(sel, data, 0), ("replica", "part"))
        lens = jax.lax.psum(jnp.where(sel, lens, 0), ("replica", "part"))
        count = jax.lax.psum(jnp.where(sel, count, zero), ("replica", "part"))
        return data, lens, count

    smapped_read = _smap(
        read_body,
        mesh,
        in_specs=(st_specs, P("replica"), P(), P(), P()),
        out_specs=(P(), P(), P()),
    )

    @jax.jit
    def _read(state, replica, partition, offset):
        replica = jnp.clip(replica, 0, R - 1)
        partition = jnp.clip(partition, 0, cfg.partitions - 1)
        return smapped_read(state, rep_ids, replica, partition, offset)

    # Batched reads: Q queries, ONE dispatch, one psum for the whole
    # batch (the consume-side mirror of append batching).
    def read_many_body(state, rep, replicas, partitions, offsets):
        st = _squeeze(state)
        my_rep = rep[0]
        my_shard = jax.lax.axis_index("part")

        def one(replica, partition, offset):
            shard = partition // local_P
            local_idx = partition % local_P
            data, lens, count = core_step.read_batch(cfg, st, local_idx,
                                                     offset)
            sel = (my_rep == replica) & (my_shard == shard)
            return (
                jnp.where(sel, data, 0),
                jnp.where(sel, lens, 0),
                jnp.where(sel, count, jnp.int32(0)),
            )

        data, lens, count = jax.vmap(one)(replicas, partitions, offsets)
        data = jax.lax.psum(data, ("replica", "part"))
        lens = jax.lax.psum(lens, ("replica", "part"))
        count = jax.lax.psum(count, ("replica", "part"))
        return data, lens, count

    smapped_read_many = _smap(
        read_many_body,
        mesh,
        in_specs=(st_specs, P("replica"), P(), P(), P()),
        out_specs=(P(), P(), P()),
    )

    @jax.jit
    def _read_many(state, replicas, partitions, offsets):
        replicas = jnp.clip(replicas, 0, R - 1)
        partitions = jnp.clip(partitions, 0, cfg.partitions - 1)
        return smapped_read_many(state, rep_ids, replicas, partitions,
                                 offsets)

    def read_off_body(state, rep, replica, partition, consumer_slot):
        st = _squeeze(state)
        shard = partition // local_P
        local_idx = partition % local_P
        sel = (rep[0] == replica) & (jax.lax.axis_index("part") == shard)
        val = core_step.read_offset(st, local_idx, consumer_slot)
        return jax.lax.psum(jnp.where(sel, val, 0), ("replica", "part"))

    smapped_read_off = _smap(
        read_off_body,
        mesh,
        in_specs=(st_specs, P("replica"), P(), P(), P()),
        out_specs=P(),
    )

    @jax.jit
    def _read_offset(state, replica, partition, consumer_slot):
        replica = jnp.clip(replica, 0, R - 1)
        partition = jnp.clip(partition, 0, cfg.partitions - 1)
        return smapped_read_off(state, rep_ids, replica, partition, consumer_slot)

    # ---- resync -----------------------------------------------------------
    def resync_body(state, rep, src, dst, part_mask):
        # The masking below assumes [local_P, ...] leaves; the ctrl leaf
        # is [K, local_P]. Resync is the rare recovery path, so
        # round-trip through the named layout (exact both ways) instead
        # of teaching the masking about the stacked axis — the same
        # trade the local binding makes.
        st = unfuse_state(_squeeze(state))
        my_rep = rep[0]
        # broadcast src replica's masked rows to everyone, then overwrite dst
        def leaf(x):
            m = part_mask.reshape((-1,) + (1,) * (x.ndim - 1))
            src_rows = jax.lax.psum(
                jnp.where((my_rep == src) & m, x, jnp.zeros_like(x)), "replica"
            )
            return jnp.where((my_rep == dst) & m, src_rows, x)

        return _expand(fuse_state(jax.tree.map(leaf, st)))

    smapped_resync = _smap(
        resync_body,
        mesh,
        in_specs=(st_specs, P("replica"), P(), P(), P("part")),
        out_specs=st_specs,
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _resync_fn(state, src, dst, part_mask):
        return smapped_resync(state, rep_ids, src, dst, part_mask)

    # ---- init -------------------------------------------------------------
    def _place(one: ReplicaState):
        """Install a single-replica image (the NAMED layout — the
        recovery path hands plain ReplicaStates) on every replica slot,
        sharded per st_specs; the ctrl scalars are stacked first so the
        placed state matches the compiled layout."""
        one = fuse_state(one)
        full = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x), (R,) + jnp.asarray(x).shape),
            one,
        )
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), st_specs,
                                 is_leaf=lambda s: isinstance(s, P))
        return jax.tree.map(jax.device_put, full, shardings)

    def _init():
        return _place(init_state(cfg))

    return SpmdEngineFns(_init, _step, _step_many, _step_sparse,
                         _step_many_sparse, _vote, _read, _read_many,
                         _read_offset, _resync_fn, _place, backend, mesh)
