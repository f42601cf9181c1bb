"""Static engine configuration.

Every field here is a *shape* as far as XLA is concerned: the whole data
plane is traced once per EngineConfig and never recompiled. Membership
changes, leader changes and partition starts/stops are expressed as masked
*values* (alive masks, leader ids, counts), never as shape changes — see
SURVEY.md §7 "hard parts".
"""

from __future__ import annotations

import dataclasses
import warnings

# Log slot alignment: every committed round advances the log end to a
# multiple of ALIGN so that the append kernel's DMA windows land on TPU
# sublane-tile boundaries (Mosaic requires row offsets divisible by the
# uint8 sublane tile of 8). Consequence: offsets are STORAGE offsets —
# dense within a round, with up to ALIGN-1 empty padding slots between
# rounds; the wire protocol therefore always reports `next_offset`
# explicitly instead of letting clients compute `offset + n` (a documented
# deviation from the reference's dense-offset arithmetic,
# ConsumerClientImpl.java:103-109).
ALIGN = 8

# Bytes reserved at the head of every log row for metadata:
#   [0:4)  payload length, little-endian int32 (0 = empty/padding row)
#   [4:8)  Raft term of the writing round, little-endian int32
# Embedding the header in the row keeps the data plane to ONE array and
# the append to ONE DMA per (replica, partition) per round.
ROW_HEADER = 8

# Ring-stride aliasing hazard: when the
# per-partition ring stride (slots + max_batch) * slot_bytes lands on or
# near a power of two >= 2^20, the append kernel's strided partition DMAs
# alias HBM channels and the measured write rate drops 25-35% (slots 8192
# at SB 128 — stride 2^20 + 32 KiB — vs slots 8448/12352 in the same
# process). The measured-bad stride sat 3.1% off the power of two, so the
# "near" band is 1/16 relative.
STRIDE_POW2_FLOOR = 1 << 20
_STRIDE_REL_TOL = 16  # flag within pow2/16 of the power of two
# Below this many partition rings RESIDENT ON ONE DEVICE there are too
# few concurrent strided streams to alias measurably. The count is a
# per-device property, not a config property: the local (vmap) binding
# keeps every replica's rings on one chip (partitions * replicas
# streams), while the spmd binding's devices each hold ONE replica's
# shard (partitions / part_shards streams — parallel.engine re-prices
# the hazard there, since the config cannot know the mesh).
STRIDE_WARN_MIN_PARTITIONS = 64


def ring_stride_bytes(slots: int, max_batch: int, slot_bytes: int) -> int:
    """Per-partition byte stride of the physical log array
    [P, slots + max_batch, slot_bytes] (the ring plus its wrap margin)."""
    return (slots + max_batch) * slot_bytes


def stride_alias_hazard(slots: int, max_batch: int, slot_bytes: int,
                        streams: int | None = None) -> str | None:
    """Non-None iff the ring stride lands on/near a >= 2^20 power of two
    (the HBM-channel-aliasing shapes; see STRIDE_POW2_FLOOR). Returns the
    warning text so callers can warn, log, or assert on it.

    `streams` is the number of partition rings resident on ONE device —
    the concurrent strided-DMA streams that actually hammer the HBM
    channels. Below STRIDE_WARN_MIN_PARTITIONS the aliasing is
    unmeasurable and the verdict is None regardless of the stride:
    pricing the GLOBAL partition count instead gets sharded deployments
    wrong in both directions (a P=1024 config sharded 32 ways leaves 32
    rings per device — clean — while a P=32 R=3 local binding keeps 96
    rings on one chip — hazardous). None = stride-only verdict (the
    caller applies its own stream gate)."""
    if streams is not None and streams < STRIDE_WARN_MIN_PARTITIONS:
        return None
    stride = ring_stride_bytes(slots, max_batch, slot_bytes)
    if stride <= 0:
        return None
    lo = 1 << (stride.bit_length() - 1)
    for pow2 in (lo, lo << 1):
        if pow2 >= STRIDE_POW2_FLOOR and (
            abs(stride - pow2) <= pow2 // _STRIDE_REL_TOL
        ):
            return (
                f"ring stride {stride} B/partition "
                f"((slots={slots} + max_batch={max_batch}) * "
                f"slot_bytes={slot_bytes}) is within {100 / _STRIDE_REL_TOL:.1f}% "
                f"of 2^{pow2.bit_length() - 1}; strided append DMAs at this "
                f"shape alias HBM channels (measured 25-35% write-rate "
                f"penalty). Nudge `slots` so the stride "
                f"moves off the power of two."
            )
    return None


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Shape/config of one replication-engine program.

    The reference runs one JRaft group per topic-partition, all multiplexed
    on a single RPC server (reference:
    mq-broker/src/main/java/metadata/raft/PartitionRaftServer.java:93).
    Here the multiplexing is a tensor axis: `partitions` is the leading
    vmap axis of every state array.
    """

    partitions: int = 8          # P — total partition slots in the program
    replicas: int = 3            # R — replication factor == mesh axis size
    slots: int = 1024            # S — log capacity per partition (entries)
    slot_bytes: int = 128        # SB — bytes per log slot (incl. ROW_HEADER)
    max_batch: int = 32          # B — max appended entries per partition/step
    read_batch: int = 32         # RB — max entries per batch read
    max_consumers: int = 64      # C — consumer-offset table width
    max_offset_updates: int = 8  # U — max offset commits per partition/step
    # Host-path knob (NOT a device shape — no recompile): how many
    # dispatched rounds may have their standby replication in flight
    # while the device advances. Acks and the settled-read horizon are
    # released strictly in round order; the window backpressures when
    # full and drains on any fencing/deposition/membership event, so the
    # chaos plane's handover invariants hold verbatim at any width
    # (broker/dataplane.py settle pipeline). 1 = legacy serialized
    # settle (each round's acks land before the next round's release).
    settle_window: int = 4

    def __post_init__(self) -> None:
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if self.settle_window < 1:
            raise ValueError("settle_window must be >= 1")
        if self.max_batch > self.slots:
            raise ValueError("max_batch cannot exceed slots")
        if self.read_batch > self.slots:
            raise ValueError("read_batch cannot exceed slots")
        if self.slot_bytes <= ROW_HEADER:
            raise ValueError(f"slot_bytes must exceed the {ROW_HEADER}-byte row header")
        if self.max_batch % ALIGN:
            raise ValueError(f"max_batch must be a multiple of {ALIGN}")
        if self.slots % ALIGN:
            raise ValueError(f"slots must be a multiple of {ALIGN}")
        # The aliasing penalty comes from MANY concurrent strided
        # partition DMAs hammering the same HBM channels; at small
        # per-device ring counts the effect is negligible (the shipped
        # P=8 example keeps its round numbers on purpose — see
        # examples/cluster.yaml's sizing note), so only fan-out shapes
        # warn. The stream count priced here is the DEFAULT local
        # binding's: one device holds every replica's rings (P * R). A
        # sharded deployment's devices hold only partitions/part_shards
        # rings each — parallel.engine.make_spmd_fns re-prices the
        # hazard at that per-device shard and is the authority there.
        hazard = stride_alias_hazard(self.slots, self.max_batch,
                                     self.slot_bytes,
                                     streams=self.partitions * self.replicas)
        if hazard is not None:
            warnings.warn(hazard, UserWarning, stacklevel=2)

    @property
    def quorum(self) -> int:
        """Majority of the full membership (Raft quorum)."""
        return self.replicas // 2 + 1

    @property
    def payload_bytes(self) -> int:
        """Max message payload per slot (slot minus the row header)."""
        return self.slot_bytes - ROW_HEADER
