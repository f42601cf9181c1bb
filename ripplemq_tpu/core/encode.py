"""Host-side encoding between Python payloads and fixed-shape step tensors.

This is the boundary where variable-length byte-string messages become
slotted fixed-shape arrays (SURVEY.md §7 "hard parts" #1): each payload is
packed into one `slot_bytes` uint8 row behind an 8-byte header (length +
round term, little-endian — see core.config.ROW_HEADER). The broker
batcher and the test suite share these builders so there is exactly one
encoder (the reference's equivalent boundary is Java serialization of
`List<String>` request DTOs,
mq-common/src/main/java/request/partition/MessageAppendRequest.java).
"""

from __future__ import annotations

import numpy as np

from ripplemq_tpu.core.config import ALIGN, ROW_HEADER, EngineConfig
from ripplemq_tpu.core.state import StepInput


def row_extents(counts: np.ndarray) -> np.ndarray:
    """Per-partition write extents (rows, ALIGN-rounded) from payload
    counts — what the write phase needs to clip each append DMA to the
    bytes the round actually carries. Host-side analogue of core.step._padded_advance."""
    counts = np.asarray(counts, np.int32)
    return ((counts + ALIGN - 1) // ALIGN * ALIGN).astype(np.int32)


def pack_rows(
    cfg: EngineConfig, payloads: list[bytes], term: int
) -> np.ndarray:
    """Pack payloads into a [B, SB] block of header-prefixed rows.

    Rows beyond len(payloads) carry length 0 and the round term — they
    are the round's ALIGN padding and must still hold a valid term (the
    log-matching check reads the tail row's term, whether or not it holds
    a payload)."""
    B, SB = cfg.max_batch, cfg.slot_bytes
    if len(payloads) > B:
        raise ValueError(f"{len(payloads)} payloads > max_batch {B}")
    rows = np.zeros((B, SB), np.uint8)
    rows[:, 4:8] = np.frombuffer(
        np.int32(term).tobytes(), np.uint8
    )  # little-endian term in every row
    for i, m in enumerate(payloads):
        if not isinstance(m, (bytes, bytearray, memoryview)):
            raise TypeError(f"payloads must be bytes, got {type(m).__name__}")
        m = bytes(m)
        if not m:
            raise ValueError("empty messages are not supported (length-0 "
                             "rows mark alignment padding)")
        if len(m) > cfg.payload_bytes:
            raise ValueError(
                f"payload of {len(m)} bytes > payload_bytes {cfg.payload_bytes}"
            )
        rows[i, 0:4] = np.frombuffer(np.int32(len(m)).tobytes(), np.uint8)
        rows[i, ROW_HEADER : ROW_HEADER + len(m)] = np.frombuffer(m, np.uint8)
    return rows


def pack_payload_rows(cfg: EngineConfig, payloads: list[bytes]) -> np.ndarray:
    """Pack payloads into a [len(payloads), SB] block of header-prefixed
    rows with a ZERO term field — the batcher stamps the round term over
    the rows a round carries at drain time, in its own copy (the term is
    a round property, unknown at submit). Splitting the packing from the term
    stamp lets the per-message work run on the submitting thread (RPC
    workers, in parallel) instead of inside the batcher's lock, where it
    serialized the whole data plane under deep backlogs. Callers
    validate payload sizes/types first (DataPlane.submit_append).

    Uniform-length batches (every producer SDK batch in practice) take a
    vectorized path: ONE join + ONE reshape instead of a python loop of
    per-row numpy assignments — the loop was ~1.2 ms per 256-row batch
    on the profiled host, most of the host's per-message packing cost
    (PROFILE.md "host path")."""
    SB = cfg.slot_bytes
    k = len(payloads)
    rows = np.zeros((k, SB), np.uint8)
    n0 = len(payloads[0]) if k else 0
    if k and all(len(m) == n0 for m in payloads):
        rows[:, 0:4] = np.frombuffer(
            np.full((k,), n0, "<i4").tobytes(), np.uint8
        ).reshape(k, 4)
        rows[:, ROW_HEADER : ROW_HEADER + n0] = np.frombuffer(
            b"".join(payloads), np.uint8
        ).reshape(k, n0)
        return rows
    for i, m in enumerate(payloads):
        n = len(m)
        rows[i, 0:4] = np.frombuffer(np.int32(n).tobytes(), np.uint8)
        rows[i, ROW_HEADER : ROW_HEADER + n] = np.frombuffer(m, np.uint8)
    return rows


def build_step_input(
    cfg: EngineConfig,
    appends: dict[int, list[bytes]] | None = None,
    offset_updates: dict[int, list[tuple[int, int]]] | None = None,
    leader: dict[int, int] | int = -1,
    term: dict[int, int] | int = 0,
) -> StepInput:
    """Build one round's StepInput from plain Python values.

    `appends` maps partition -> payload list (each <= cfg.payload_bytes,
    at most cfg.max_batch per partition); `offset_updates` maps
    partition -> [(consumer_slot, absolute_offset)]; `leader`/`term` are
    per-partition dicts or one value for all partitions. Raises ValueError
    on oversized payloads or batches — the batcher enforces these limits
    before building, so a trip here is a bug, not backpressure.
    """
    P, B, SB, U = cfg.partitions, cfg.max_batch, cfg.slot_bytes, cfg.max_offset_updates

    def _per_partition(value, default):
        arr = np.full((P,), default, np.int32)
        if isinstance(value, dict):
            for p, v in value.items():
                if not 0 <= p < P:
                    raise ValueError(f"partition {p} out of range [0, {P})")
                arr[p] = v
        else:
            arr[:] = value
        return arr

    terms = _per_partition(term, 0)
    entries = np.zeros((P, B, SB), np.uint8)
    counts = np.zeros((P,), np.int32)
    off_slots = np.zeros((P, U), np.int32)
    off_vals = np.zeros((P, U), np.int32)
    off_counts = np.zeros((P,), np.int32)

    for p, msgs in (appends or {}).items():
        if not 0 <= p < P:
            raise ValueError(f"partition {p} out of range [0, {P})")
        entries[p] = pack_rows(cfg, msgs, int(terms[p]))
        counts[p] = len(msgs)

    for p, ups in (offset_updates or {}).items():
        if not 0 <= p < P:
            raise ValueError(f"partition {p} out of range [0, {P})")
        if len(ups) > U:
            raise ValueError(
                f"partition {p}: {len(ups)} offset updates > max_offset_updates {U}"
            )
        for i, (slot, off) in enumerate(ups):
            off_slots[p, i] = slot
            off_vals[p, i] = off
        off_counts[p] = len(ups)

    return StepInput(
        entries=entries,
        counts=counts,
        off_slots=off_slots,
        off_vals=off_vals,
        off_counts=off_counts,
        leader=_per_partition(leader, -1),
        term=terms,
        extents=row_extents(counts),
    )


def decode_entries(data, lens, count) -> list[bytes]:
    """Messages from a batch read's (rows, lens, count). Length-0 rows are
    alignment padding, not messages — skipped."""
    return [m for _, m in decode_entries_with_pos(data, lens, count)]


def decode_entries_with_pos(data, lens, count) -> list[tuple[int, bytes]]:
    """Like decode_entries but yields (row_index, payload) so callers can
    turn a truncated message list back into a storage offset."""
    data, lens, count = np.asarray(data), np.asarray(lens), int(count)
    out = []
    for i in range(count):
        n = int(lens[i])
        if n > 0:
            out.append((i, bytes(data[i, ROW_HEADER : ROW_HEADER + n].tobytes())))
    return out
