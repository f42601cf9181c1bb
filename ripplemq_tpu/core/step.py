"""Pure Raft data-plane steps, written against a named "replica" axis.

This module is the TPU-native replacement for the reference's hot loop:
JRaft AppendEntries replication + per-entry quorum ack + state-machine
apply (reference call stack: MessageAppendRequestProcessor.java:59 →
JRaft replication → PartitionStateMachine.onApply:38). There, each message
is one Raft task on one of many per-partition JVM actor groups. Here, ONE
jitted step replicates a (partition × entry) batch across every replica
and advances every partition's commit index in a single psum round:

  1. Every replica receives the round's batch (the broadcast over the
     replica axis is the AppendEntries transfer; under SPMD it rides ICI).
  2. A replica *acks* iff it is alive, its log end matches the leader's
     pre-append log end AND its tail term matches the leader's (the full
     Raft log-matching check) and the leader's term is current.
  3. votes = lax.psum(ack) over the replica axis — the ballot happens
     BEFORE any write (the ack predicate only reads pre-round state).
  4. Rounds are atomic: iff the ballot reached quorum, acking replicas
     append the batch and advance commit; a failed round leaves no trace
     on any replica, so retries are always safe. (Wire Raft instead lets
     leader/follower logs diverge and repairs them with nextIndex
     backtracking — pointless here, where ballot + write are one fused
     device program.)
  5. Committed consumer-offset updates blend into the replicated offset
     table in the same round (the reference routes them through the same
     per-partition Raft log — PartitionStateMachine.java:71-77).

The step is split in two phases for the hardware's sake:
- `replica_control` — everything EXCEPT the log write: acks, ballot,
  commit bookkeeping, offset-table blend. Cheap [P]-shaped vector ops;
  runs per replica under vmap (local) or shard_map (SPMD).
- the log write — the round's extent of one [B, SB] block per committed
  partition at a variable, ALIGN-aligned offset. This is
  `ripplemq_tpu.ops.append` (Pallas DMA kernel on TPU; XLA scatter
  fallback), called once on the full [R, P, S, SB] log by the engine
  wrappers, NOT per replica.

Each committed round advances log_end to the next ALIGN boundary; padding
rows carry length 0 and the round's term (core.config.ALIGN rationale).

Rare, branchy transitions (elections, membership, resync after a replica
returns from the dead) are host-coordinated; the per-step path is
branch-free so XLA compiles it once per EngineConfig. Leader election's
vote *counting* does run on device (`vote_step`) as a psum reduction.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ripplemq_tpu.core.config import ALIGN, EngineConfig
from ripplemq_tpu.core.state import (
    FusedReplicaState,
    StepInput,
    StepOutput,
    row_lens,
)

AXIS = "replica"


def _bcast_from_leader(value: jax.Array, is_leader: jax.Array) -> jax.Array:
    """Broadcast a per-replica value from each partition's leader to all
    replicas: mask to the leader's contribution, sum over the replica axis.
    `value`/`is_leader` are [P]-shaped per-replica arrays."""
    contrib = jnp.where(is_leader, value, jnp.zeros_like(value))
    return lax.psum(contrib, AXIS)


def _normalize_alive(alive: jax.Array, P: int, R: int) -> jax.Array:
    """Accept a [R] cluster-wide or [P, R] per-partition liveness mask.

    Per-partition masks exist because each partition maps its replica
    slots to different brokers (sticky assignment): one dead broker kills
    slot 2 of one partition and slot 0 of another (the reference's
    per-group peer lists, PartitionRaftServer.java:83).
    """
    if alive.ndim == 1:
        return jnp.broadcast_to(alive[None, :], (P, R))
    return alive


def _padded_advance(counts: jax.Array) -> jax.Array:
    """Slots consumed by a round: counts rounded up to ALIGN (0 stays 0)."""
    return ((counts + ALIGN - 1) // ALIGN) * ALIGN


class ControlOut(NamedTuple):
    out: StepOutput     # per-partition round results (replica-invariant)
    do_write: jax.Array  # bool [P] — this replica writes the round's block
    extent: jax.Array    # int32 [P] — rows of the [B, SB] window the write
    #                      phase covers (the host's extent, or B where
    #                      the input names none; replica-invariant —
    #                      derived from the input)


def _write_extent(cfg: EngineConfig, inp: StepInput,
                  advance: jax.Array) -> jax.Array:
    """Rows the write phase covers: the host-declared extent, ALIGN-
    rounded and clamped to [advance, B] so a committed round's rows are
    always covered no matter what the host fed. An input without extents
    (hand-built, pytree-empty None) means the full B-row window — the
    one place that is decided."""
    B = cfg.max_batch
    if inp.extents is None:
        return jnp.full_like(advance, B)
    ext = _padded_advance(jnp.clip(inp.extents, 0, B))
    return jnp.clip(ext, advance, B)


def _blend_offsets(cfg: EngineConfig, state_offsets: jax.Array,
                   inp: StepInput, do_write: jax.Array) -> jax.Array:
    """Committed consumer-offset updates: blended (not scattered —
    scatters row-serialize on TPU) into the [P, C] table; U is small and
    static, so the update unrolls to U masked selects."""
    U = cfg.max_offset_updates
    C = cfg.max_consumers
    off_counts = jnp.clip(inp.off_counts, 0, U)
    new_offsets = state_offsets
    cols = jnp.arange(C, dtype=jnp.int32)[None, :]         # [1, C]
    for u in range(U):
        apply_u = do_write & (u < off_counts)              # [P]
        mask = (inp.off_slots[:, u : u + 1] == cols) & apply_u[:, None]
        new_offsets = jnp.where(mask, inp.off_vals[:, u : u + 1], new_offsets)
    return new_offsets


def replica_control(
    cfg: EngineConfig,
    state: FusedReplicaState,
    inp: StepInput,
    rep_idx: jax.Array,   # int32 scalar — this replica's id on the axis
    alive: jax.Array,     # bool [R] or [P, R] — membership mask (replicated)
    quorum: jax.Array | None = None,  # int32 [P] — per-partition quorum
    trim: jax.Array | None = None,    # int32 [P] — retention watermark
) -> tuple[FusedReplicaState, ControlOut]:
    """One round's control phase from one replica's point of view: the
    ballot and all scalar-state updates. The returned state has every
    field advanced EXCEPT `log_data` (the write phase owns that).

    `quorum` is per-partition because topics can carry different
    replication factors than the mesh's replica-axis size: a partition
    with RF 3 on an R=5 program commits at 2 acks, with its two unused
    slots permanently masked dead in `alive`.

    `trim` is the host's retention watermark (absolute offset, identical
    on every replica — it rides the round input like `alive`): ring rows
    holding offsets below `trim` are reclaimable, so a round fits iff its
    full B-row window only ever lands on free-or-reclaimable rows
    (`base + B - trim <= S`). Host contracts: trim is monotone per
    partition, never exceeds the persisted/committed prefix, and the
    host clamps each round's batch so `advance <= S - (base % S)` (live
    rows never land in the wrap margin — see core.state ring doc).

    The bookkeeping works on the stacked [K, P] ctrl buffer because the
    control phase's cost is fusion-boundary overhead across small
    element-wise ops, not arithmetic:
    - the two leader broadcasts (prevLogIndex + prevLogTerm) ride ONE
      [2, P] psum — under shard_map one collective on the replica mesh
      axis per round, under vmap one reduction;
    - the four bookkeeping advances are ONE [K, P] select on one buffer
      (`maximum(x, y)` == `where(y > x, y, x)` bitwise for int32, which
      writes current_term/commit as selects beside log_end/last_term);
    - the scan carry of a chained launch is three leaves.
    """
    S, B, R = cfg.slots, cfg.max_batch, cfg.replicas
    # Shard-shape note: under shard_map this function sees [local_P]
    # SHARDS of every per-partition argument, not the global [P] — all
    # the arithmetic below is shape-agnostic, but these cfg.partitions-
    # shaped defaults are NOT, so the spmd wrappers always pass quorum/
    # trim explicitly (parallel.engine fills them before the smapped
    # call). The defaults exist for the local binding and direct use.
    P = cfg.partitions
    if quorum is None:
        quorum = jnp.full((P,), cfg.quorum, jnp.int32)
    if trim is None:
        trim = jnp.zeros((P,), jnp.int32)

    ctrl = state.ctrl                                     # [K, P]
    log_end, last_term = ctrl[0], ctrl[1]
    current_term, commit = ctrl[2], ctrl[3]

    # Sanitize host-fed control values: an out-of-range index is undefined
    # behavior on TPU gathers (observed: backend InvalidArgument), and an
    # oversized count would advance log_end past what was written
    # (phantom committed entries).
    counts = jnp.clip(inp.counts, 0, B)
    advance = _padded_advance(counts)                    # [P]

    alive = _normalize_alive(alive, P, R)                # [P, R]
    self_alive = alive[:, rep_idx]                       # [P]
    leader_known = (inp.leader >= 0) & (inp.leader < R)  # [P]
    is_leader = (inp.leader == rep_idx) & leader_known   # [P]
    leader_alive = jnp.where(
        leader_known,
        jnp.take_along_axis(
            alive, jnp.clip(inp.leader, 0, R - 1)[:, None], axis=1
        )[:, 0],
        False,
    )

    # --- 1. leader's pre-append log end ("prevLogIndex" of AppendEntries)
    # and the term of its tail row ("prevLogTerm"; cached in state), as
    # ONE stacked psum: mask to the leader's contribution, sum over the
    # replica axis.
    lead_mask = (is_leader & self_alive)[None, :]         # [1, P]
    led = lax.psum(
        jnp.where(lead_mask, ctrl[0:2], jnp.zeros_like(ctrl[0:2])), AXIS
    )                                                     # [2, P]
    base, leader_last_term = led[0], led[1]

    # --- 2. ack: alive + log-matching + term current. Log matching is the
    # full Raft check — prevLogIndex (log_end == base) AND prevLogTerm:
    # a replica whose log is the same length but whose tail was written
    # under a different term has a divergent suffix and must NOT ack (it
    # re-enters via host-driven resync).
    term_ok = inp.term >= current_term
    log_match = (log_end == base) & (
        (base == 0) | (last_term == leader_last_term)
    )
    # Capacity is priced at the full B-row window whatever extent the
    # write phase covers this round: on the ring (previous lap) the
    # window covers absolute offsets [base - S, base + B - S) — all of
    # which must be below the trim watermark. With trim pinned at 0 this
    # reduces to the bounded-log rule base + B <= S. Offsets-only rounds
    # (counts == 0) consume no log space and must keep committing on a
    # full partition: consumers still need to advance their positions
    # through the backlog.
    capacity_ok = (counts == 0) | (base + B - trim <= S)
    # A round is ack-worthy if it carries entries OR offset commits: offset
    # commits on idle partitions must still replicate (the reference routes
    # them through the partition Raft log regardless of appends).
    has_work = (counts > 0) | (inp.off_counts > 0)
    ack = (
        self_alive
        & leader_alive
        & term_ok
        & log_match
        & capacity_ok
        & has_work
    )  # [P]

    # --- 3. ballot before any write.
    votes = lax.psum(ack.astype(jnp.int32), AXIS)          # [P]
    committed = votes >= quorum                            # [P]
    do_write = ack & committed                             # [P]

    # --- 4. scalar state advances (atomic with the ballot), as one wide
    # select. wrote_rows additionally gates the write phase: offsets-only
    # rounds must not pay the (hottest-op) append DMA for an all-zero
    # window.
    wrote_rows = do_write & (advance > 0)
    adv_target = base + advance
    conds = jnp.stack([
        wrote_rows,                                        # log_end
        wrote_rows,                                        # last_term
        inp.term > current_term,                           # current_term
        do_write & (adv_target > commit),                  # commit
    ])                                                     # [K, P] bool
    cands = jnp.stack([adv_target, inp.term, inp.term, adv_target])
    new_ctrl = jnp.where(conds, cands, ctrl)               # [K, P]

    # --- 5. committed consumer-offset updates.
    new_offsets = _blend_offsets(cfg, state.offsets, inp, do_write)

    new_state = state._replace(ctrl=new_ctrl, offsets=new_offsets)
    out = StepOutput(
        base=base,
        votes=votes,
        committed=committed,
        commit=lax.pmax(new_ctrl[3], AXIS),
    )
    return new_state, ControlOut(out, wrote_rows, _write_extent(cfg, inp, advance))


def replica_step(
    cfg: EngineConfig,
    state: FusedReplicaState,
    inp: StepInput,
    rep_idx: jax.Array,
    alive: jax.Array,
    quorum: jax.Array | None = None,
    trim: jax.Array | None = None,
) -> tuple[FusedReplicaState, StepOutput]:
    """Complete per-replica round: control phase + per-replica XLA append.

    This is the portable all-in-one composition (works under plain vmap on
    any backend, e.g. the driver's single-chip compile check). The engine
    wrappers instead run `replica_control` under vmap/shard_map and hand
    the write phase to the batched Pallas kernel (ops.append) — same
    semantics, asserted by tests. The write lands at the PHYSICAL ring
    position `base % slots` (base itself is absolute).
    """
    new_state, ctl = replica_control(cfg, state, inp, rep_idx, alive, quorum,
                                     trim)
    from ripplemq_tpu.ops.append import append_rows_xla  # local: avoid cycle

    log_data = append_rows_xla(
        state.log_data, inp.entries, ctl.out.base % cfg.slots, ctl.do_write,
        ctl.extent,
    )
    return new_state._replace(log_data=log_data), ctl.out


def vote_step(
    cfg: EngineConfig,
    state: FusedReplicaState,
    cand: jax.Array,       # int32 [P] — candidate replica id per partition (-1 = no election)
    cand_term: jax.Array,  # int32 [P] — candidate's proposed term
    rep_idx: jax.Array,
    alive: jax.Array,
    quorum: jax.Array | None = None,  # int32 [P]
) -> tuple[FusedReplicaState, jax.Array, jax.Array]:
    """One RequestVote round: grants counted as a psum reduction.

    Returns (state', elected[P] bool, votes[P] int32); the term grant
    lands in ctrl row 2. The up-to-date check is Raft §5.4.1: grant only
    to candidates whose log is at least as complete. Replaces JRaft's
    per-group ballot (NodeOptions.setElectionTimeoutMs — reference
    PartitionRaftServer.java:85 — with timeouts host-vectorized).
    """
    R = cfg.replicas
    log_end, last_term, current_term = (
        state.ctrl[0], state.ctrl[1], state.ctrl[2])
    alive = _normalize_alive(alive, cfg.partitions, R)  # [P, R]
    if quorum is None:
        quorum = jnp.full((cfg.partitions,), cfg.quorum, jnp.int32)
    electing = (cand >= 0) & (cand < R)
    is_cand = (cand == rep_idx) & electing
    self_alive = alive[:, rep_idx]
    cand_alive = jnp.where(
        electing,
        jnp.take_along_axis(alive, jnp.clip(cand, 0, R - 1)[:, None], axis=1)[:, 0],
        False,
    )

    c_end = _bcast_from_leader(log_end, is_cand & self_alive)
    c_last_term = _bcast_from_leader(last_term, is_cand & self_alive)

    up_to_date = (c_last_term > last_term) | (
        (c_last_term == last_term) & (c_end >= log_end)
    )
    grant = electing & self_alive & cand_alive & (cand_term > current_term) & up_to_date

    votes = lax.psum(grant.astype(jnp.int32), AXIS)
    elected = votes >= quorum

    new_term = jnp.where(grant, cand_term, current_term)
    new_ctrl = state.ctrl.at[2].set(new_term)
    return state._replace(ctrl=new_ctrl), elected, votes


def read_batch(
    cfg: EngineConfig,
    state: FusedReplicaState,
    partition: jax.Array,  # int32 scalar
    offset: jax.Array,     # int32 scalar — storage offset to read from
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Read up to RB *committed* rows of one partition from this replica.

    Returns (rows [RB, SB] uint8 — header-prefixed, lens [RB] int32,
    count int32). `count` counts storage rows (including length-0
    alignment padding; decode_entries skips those), so the caller's next
    storage offset is `offset + count`. Serves the consume path; like the
    reference this is a replica-local read with no extra consensus round
    (PartitionStateMachine.handleBatchRead:85 — leader-local, no
    read-index), but unlike the reference it only exposes rows below the
    commit index.

    `offset` is an ABSOLUTE storage offset; the physical row of offset
    `a` is `a % slots` (ring — see core.state). The read window may wrap
    the ring end, so rows are blended from two windows: [pos, pos+RB)
    (clamped+rolled) and the ring head [0, RB). Host contract: offset is
    at least the host's trim watermark — ring rows below trim may have
    been reclaimed (the host serves those from the segment store).
    """
    return read_batch_at(
        cfg, state.log_data[None], state.commit[None], jnp.int32(0),
        partition, offset,
    )


def read_batch_at(
    cfg: EngineConfig,
    log_data: jax.Array,   # uint8 [R, P, S+B, SB] — FULL log, no copy
    commit: jax.Array,     # int32 [R, P]
    replica: jax.Array,    # int32 scalar
    partition: jax.Array,  # int32 scalar
    offset: jax.Array,     # int32 scalar — absolute storage offset
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """read_batch addressing the full multi-replica log with dynamic
    slices — NO whole-replica gather. This matters under vmap (batched
    reads, ops-level: engine read_many): `tree.map(x[replica])` per query
    would materialize a [P, S, SB] copy of the log PER QUERY; here each
    query moves exactly 2xRB rows."""
    RB, S = cfg.read_batch, cfg.slots
    SP = S + cfg.max_batch  # physical rows incl. wrap margin
    R = log_data.shape[0]
    replica = jnp.clip(replica, 0, R - 1)
    partition = jnp.clip(partition, 0, cfg.partitions - 1)
    com = lax.dynamic_slice(commit, (replica, partition), (1, 1))[0, 0]
    start = jnp.maximum(offset, 0)
    count = jnp.clip(com - start, 0, RB)
    pos = start % S
    # Window A: physical [pos, pos+RB). dynamic_slice clamps the start so
    # the window fits; compensate by slicing at a clamped start and
    # rolling the wanted rows to the front.
    sl_start = jnp.clip(pos, 0, SP - RB)
    shift = pos - sl_start
    rows_a = lax.dynamic_slice(
        log_data,
        (replica, partition, sl_start, 0),
        (1, 1, RB, cfg.slot_bytes),
    )[0, 0]
    rows_a = jnp.roll(rows_a, -shift, axis=0)
    # Window B: ring head [0, RB) — serves row i when pos + i wraps past
    # the ring end (margin rows are never live; see core.state).
    rows_b = lax.dynamic_slice(
        log_data, (replica, partition, 0, 0), (1, 1, RB, cfg.slot_bytes)
    )[0, 0]
    wrap_at = S - pos  # first window-index served from the ring head
    rows_b = jnp.roll(rows_b, wrap_at, axis=0)  # b[i] = head[i - wrap_at]
    i = jnp.arange(RB, dtype=jnp.int32)
    rows = jnp.where((i < wrap_at)[:, None], rows_a, rows_b)
    valid = i < count
    rows = jnp.where(valid[:, None], rows, 0)
    lens = jnp.where(valid, row_lens(rows), 0)
    return rows, lens, count


def read_offset(
    state: FusedReplicaState,
    partition: jax.Array,
    consumer_slot: jax.Array,
) -> jax.Array:
    """Current committed offset for one consumer slot."""
    P, C = state.offsets.shape
    return state.offsets[
        jnp.clip(partition, 0, P - 1), jnp.clip(consumer_slot, 0, C - 1)
    ]
