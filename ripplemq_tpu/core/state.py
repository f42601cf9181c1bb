"""Replicated data-plane state as fixed-shape arrays.

`FusedReplicaState` is the full data-plane state of ONE replica as the
device holds it: the slotted message log, the Raft bookkeeping scalars
stacked into one [K, P] ctrl array, and the consumer-offset table for
every partition hosted by the program. `ReplicaState` names the same
content field by field: it is the host-side image that recovery builds
and hands to the engine (`init_from`), never a device layout. The
reference keeps the equivalent state as `List<String> messages` +
`Map<String, Long> consumerOffsets` per partition group (reference:
mq-broker/src/main/java/metadata/raft/PartitionStateMachine.java:26-27),
purely in JVM heap; here it is a pytree of device arrays so that
replication, quorum and apply are tensor ops.

Row format: every log slot is `slot_bytes` of uint8 with an embedded
8-byte header — payload length then Raft term, both little-endian int32
(see core.config.ROW_HEADER). One array holds everything the Raft log
needs, so the append write phase is ONE DMA per (replica, partition).

Ring retention: `log_end` and `commit` are MONOTONE absolute storage
offsets; the physical log holds the last `slots` rows as a ring (row for
absolute offset `a` lives at physical row `a % slots`) plus a
`max_batch`-row margin so the append DMA's window (at most [B, SB]) never
wraps (rows landing in the margin are always beyond the round's advance —
dead padding that no read ever selects). Overwriting ring rows is gated
by a host-fed `trim` watermark (see step.replica_control): rows below
`trim` are reclaimable because the host has already persisted them to the
segment store (the disk is the log of record; the device ring is the hot
serving window). The reference instead grows partition state without
bound in JVM heap (PartitionStateMachine.java:26-27) — bounded HBM +
unbounded disk strictly dominates that over time. Offsets are int32 (the
TPU-native scalar width); the host refuses appends near the 2^31-row
per-partition horizon (broker.dataplane._OFFSET_HORIZON) rather than
letting them wrap.

Axis conventions (see EngineConfig):
  P = partitions, R = replicas, S = log slots, SB = slot bytes,
  B = append batch, C = consumer table width, U = offset-update batch.

Arrays never carry the replica axis here — the replica axis is added
either by `jax.vmap(..., axis_name="replica")` (single-device simulation)
or by sharding over a mesh axis with `shard_map` (real SPMD). The step
functions in `core.step` are written against axis name "replica" and run
unchanged under both.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ripplemq_tpu.core.config import EngineConfig


class ReplicaState(NamedTuple):
    """One replica's state with every bookkeeping vector named: the
    host-side recovery image (`DataPlane.install`, `recover_image` /
    `replay_records`, the lockstep follower's install, the engine
    bindings' `init_from`). The engine never computes on this layout."""

    log_data: jax.Array     # uint8 [P, S+B, SB] — ring rows + margin (see module doc)
    log_end: jax.Array      # int32 [P]        — next ABSOLUTE storage offset (ALIGN-padded)
    last_term: jax.Array    # int32 [P]        — term of the tail row (cached
    #                         prevLogTerm: maintained by every committed
    #                         round, travels with resync copies; avoids a
    #                         per-round row gather)
    current_term: jax.Array  # int32 [P]       — latest term this replica has seen
    commit: jax.Array       # int32 [P]        — commit index (absolute offsets
    #                         [trim, commit) are committed and ring-resident)
    offsets: jax.Array      # int32 [P, C]     — replicated consumer offsets


# Bookkeeping scalars stacked (in this order) into FusedReplicaState.ctrl.
CTRL_FIELDS = ("log_end", "last_term", "current_term", "commit")
CTRL_K = len(CTRL_FIELDS)


class FusedReplicaState(NamedTuple):
    """Per-replica data-plane state as the device holds it: the four
    per-partition bookkeeping vectors of `ReplicaState` stacked into ONE
    [K, P] int32 array.

    Rationale: the control phase's cost is fusion-boundary overhead
    across small [R, P] element-wise ops, not arithmetic. Carrying the
    scalars as one array lets the round's bookkeeping advance as a
    handful of wide ops on one buffer (core.step.replica_control) and
    keeps the scan carry of a chained launch to three leaves.

    The named accessors are what host-side readers use
    (DataPlane._fetch_state, read paths, tests); they are views, not
    extra buffers.

    Under the spmd binding the engine-stacked ctrl is [R, K, P] sharded
    ("replica", None, "part") — the K bookkeeping rows stay whole on
    every device while replicas and partitions shard
    (parallel.engine._state_specs), which is what lets the round's two
    leader broadcasts ride ONE [2, local_P] psum over the replica mesh
    axis and keeps the named-accessor views valid on process-sharded
    state (the slice is along the unsharded K axis)."""

    log_data: jax.Array     # uint8 [P, S+B, SB] — ring rows + margin (see module doc)
    ctrl: jax.Array         # int32 [K, P]       — CTRL_FIELDS, stacked
    offsets: jax.Array      # int32 [P, C]       — replicated consumer offsets

    # A leading replica axis (engine-stacked state) moves ctrl to
    # [R, K, P]; `...` keeps the accessors shape-agnostic.
    @property
    def log_end(self) -> jax.Array:
        return self.ctrl[..., 0, :]

    @property
    def last_term(self) -> jax.Array:
        return self.ctrl[..., 1, :]

    @property
    def current_term(self) -> jax.Array:
        return self.ctrl[..., 2, :]

    @property
    def commit(self) -> jax.Array:
        return self.ctrl[..., 3, :]


def fuse_state(state: ReplicaState) -> FusedReplicaState:
    """Stack a named image's bookkeeping scalars into the device layout
    (exact). This is the hand-over from recovery to the engine
    (`init_from`, `init`), and the way back in for `resync`."""
    ctrl = jnp.stack(
        [getattr(state, f) for f in CTRL_FIELDS], axis=-2
    ).astype(jnp.int32)
    return FusedReplicaState(
        log_data=state.log_data, ctrl=ctrl, offsets=state.offsets
    )


def unfuse_state(state: FusedReplicaState) -> ReplicaState:
    """Split the device layout back into named fields (exact inverse).
    Used by `resync`, whose per-partition masking wants [P]-leading
    leaves, and by tests that compare field by field."""
    return ReplicaState(
        log_data=state.log_data,
        log_end=state.log_end,
        last_term=state.last_term,
        current_term=state.current_term,
        commit=state.commit,
        offsets=state.offsets,
    )


class StepInput(NamedTuple):
    """One replication round's input (per partition).

    Fed identically to every replica by the single controller: the
    leader→follower AppendEntries transfer of the reference
    (mq-broker/.../MessageAppendRequestProcessor.java:59) is realised by
    the input's sharding layout — XLA broadcasts the batch over the
    replica mesh axis on ICI as part of data distribution.

    `entries` rows are pre-packed with headers (length + round term) by
    the host encoder; rows at index >= counts[p] carry length 0 but still
    a valid term (they become the round's alignment padding).
    """

    entries: jax.Array     # uint8 [P, B, SB] — packed rows (leader's batch)
    counts: jax.Array      # int32 [P]        — how many of B carry payloads
    off_slots: jax.Array   # int32 [P, U]     — consumer-table slots to update
    off_vals: jax.Array    # int32 [P, U]     — new absolute offsets
    off_counts: jax.Array  # int32 [P]        — how many of U are valid
    leader: jax.Array      # int32 [P]        — replica id of partition leader (-1 = none)
    term: jax.Array        # int32 [P]        — leader's term (host/election-managed)
    extents: jax.Array | None = None  # int32 [P] — rows of the [B, SB]
    #                        window the write phase must cover this round
    #                        (the host knows the payload extent at
    #                        pack time; the append DMA is clipped to it —
    #                        ops/append.py). The control phase clamps to
    #                        [advance, B], so a missing/short extent can
    #                        never under-write a committed round. None
    #                        (pytree-empty, hand-built inputs) means the
    #                        full window.


class StepOutput(NamedTuple):
    """Per-partition results of one round (identical on every replica
    after the psum — the host reads any one replica's copy)."""

    base: jax.Array        # int32 [P] — leader log_end before append (first assigned slot)
    votes: jax.Array       # int32 [P] — number of replicas that acked the round
    committed: jax.Array   # bool  [P] — quorum reached this round
    commit: jax.Array      # int32 [P] — post-round commit index


def init_state(cfg: EngineConfig) -> ReplicaState:
    """Zero image of one replica (named layout; the bindings' `init`
    stacks it like any other image)."""
    P, S, SB, C = cfg.partitions, cfg.slots, cfg.slot_bytes, cfg.max_consumers
    return ReplicaState(
        log_data=jnp.zeros((P, S + cfg.max_batch, SB), jnp.uint8),
        log_end=jnp.zeros((P,), jnp.int32),
        last_term=jnp.zeros((P,), jnp.int32),
        current_term=jnp.zeros((P,), jnp.int32),
        commit=jnp.zeros((P,), jnp.int32),
        offsets=jnp.zeros((P, C), jnp.int32),
    )


def empty_input(cfg: EngineConfig) -> StepInput:
    """An all-empty round (no appends, no offset commits, no leaders)."""
    P, B, SB, U = cfg.partitions, cfg.max_batch, cfg.slot_bytes, cfg.max_offset_updates
    return StepInput(
        entries=jnp.zeros((P, B, SB), jnp.uint8),
        counts=jnp.zeros((P,), jnp.int32),
        off_slots=jnp.zeros((P, U), jnp.int32),
        off_vals=jnp.zeros((P, U), jnp.int32),
        off_counts=jnp.zeros((P,), jnp.int32),
        leader=jnp.full((P,), -1, jnp.int32),
        term=jnp.zeros((P,), jnp.int32),
        extents=jnp.zeros((P,), jnp.int32),
    )


def row_lens(rows: jax.Array) -> jax.Array:
    """Payload lengths from packed rows' headers: uint8 [..., SB] → int32
    [...]. Little-endian, matching the host encoder (encode.pack_row)."""
    hdr = rows[..., 0:4].astype(jnp.int32)
    return hdr[..., 0] | (hdr[..., 1] << 8) | (hdr[..., 2] << 16) | (hdr[..., 3] << 24)


def row_terms(rows: jax.Array) -> jax.Array:
    """Raft terms from packed rows' headers."""
    hdr = rows[..., 4:8].astype(jnp.int32)
    return hdr[..., 0] | (hdr[..., 1] << 8) | (hdr[..., 2] << 16) | (hdr[..., 3] << 24)
