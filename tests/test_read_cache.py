"""Host-side committed-round cache: hot reads served from the host ring
mirror with ZERO device dispatch.

The reference serves a consume as a leader-local in-memory list slice —
effectively free (reference: mq-broker/src/main/java/metadata/raft/
PartitionStateMachine.java:85-110). The device ring made every hot read
pay a dispatch RTT; the mirror restores the reference's cost model (host
RAM) while keeping the quorum-committed bound (stricter than the
reference, which serves un-replicated entries)."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from ripplemq_tpu.broker.dataplane import DataPlane, replay_records
from ripplemq_tpu.storage.memstore import MemoryRoundStore
from tests.helpers import small_cfg


def _mk(cfg, **kw):
    dp = DataPlane(cfg, mode="local", store=MemoryRoundStore(), **kw)
    dp.start()
    for p in range(cfg.partitions):
        dp.set_leader(p, 0, 1)
    return dp


def test_hot_reads_hit_no_device_dispatch():
    """When the mirror covers the window, reads must never touch the
    device read path (the VERDICT-prescribed assertion)."""
    cfg = small_cfg(partitions=4, slots=256, max_batch=8, read_batch=8)
    dp = _mk(cfg)
    try:
        sent = {p: [] for p in range(4)}
        for i in range(64):
            p = i % 4
            m = b"hc-%02d-%03d" % (p, i)
            sent[p].append(m)
            dp.submit_append(p, [m]).result(timeout=30)
        for p in range(4):
            got, offset = [], 0
            while True:
                msgs, nxt = dp.read(p, offset, replica=0)
                if nxt == offset:
                    break
                got.extend(msgs)
                offset = nxt
            assert got == sent[p]
        assert dp.read_dispatches == 0, "a hot read dispatched to device"
        assert dp.read_cache_hits > 0
        # Tail polls (offset at committed end) are host-authoritative too.
        before = dp.read_cache_hits
        msgs, nxt = dp.read(0, 10_000, replica=0)
        assert msgs == [] and nxt == 10_000
        assert dp.read_dispatches == 0 and dp.read_cache_hits == before + 1
    finally:
        dp.stop()


def test_cache_parity_with_device_path():
    """The mirror and the device ring must serve byte-identical
    (messages, next_offset) walks, including max_msgs truncation."""
    cfg = small_cfg(partitions=2, slots=128, max_batch=8, read_batch=8)
    dps = [_mk(cfg), _mk(cfg, host_read_cache=False)]
    try:
        for i in range(20):
            for dp in dps:
                dp.submit_append(i % 2, [b"p-%03d-a" % i, b"p-%03d-b" % i]
                                 ).result(timeout=30)
        for limit in (None, 1, 3, 100):
            walks = []
            for dp in dps:
                got, offset, steps = [], 0, []
                while True:
                    msgs, nxt = dp.read(0, offset, replica=0,
                                        max_msgs=limit)
                    if nxt == offset:
                        break
                    got.extend(msgs)
                    steps.append((offset, nxt, len(msgs)))
                    offset = nxt
                walks.append((got, steps))
            assert walks[0] == walks[1], f"limit={limit}"
        assert dps[0].read_dispatches == 0
        assert dps[1].read_dispatches > 0
    finally:
        for dp in dps:
            dp.stop()


@pytest.mark.parametrize("slot_bytes,size", [(32, 0), (1152, 1024)])
def test_ring_wrap_serves_store_below_trim_cache_above(slot_bytes, size):
    """After the ring wraps, lagging consumers read the store below the
    trim watermark and the mirror above it — still no device dispatch.
    Also at omb-100p-1kb's width: seeded random 1,024 B records in
    1,152 B rows, five laps of the ring."""
    cfg = small_cfg(partitions=1, slots=32, max_batch=8, read_batch=8,
                    slot_bytes=slot_bytes)
    dp = _mk(cfg)
    rng = np.random.default_rng(size)
    try:
        sent = []
        for i in range(20):  # 160 rows through a 32-slot ring
            batch = [b"w-%03d-%d" % (i, j) + rng.bytes(max(0, size - 7))
                     for j in range(8)]
            sent.extend(batch)
            dp.submit_append(0, batch).result(timeout=30)
        assert int(dp.trim[0]) > 0, "ring never wrapped"
        got, offset = [], 0
        while True:
            msgs, nxt = dp.read(0, offset, replica=0)
            if nxt == offset:
                break
            got.extend(msgs)
            offset = nxt
        assert got == sent
        assert dp.read_dispatches == 0
    finally:
        dp.stop()


@pytest.mark.parametrize("slot,offset,k", [
    (0, 0, 8),       # a window at the ring's start
    (2, 5, 11),      # inside the ring, not the first partition
    (3, 27, 9),      # across the ring's wrap: rows 27-31, then 0-3
    (1, 32 + 30, 2), # a later lap: position = offset mod slots
    (3, 31, 1),      # the ring's last row alone
])
def test_cache_rows_are_the_ring_bytes(slot, offset, k):
    """`_cache_rows` slices a window off the ring's FLAT bytes (a numpy
    copy of a window lets go of the interpreter, PR 45): the same bytes
    as the [partition, slot, byte] view's rows, wrap included, and
    `_decode_rows` reads them as it read the array."""
    cfg = small_cfg(partitions=4, slots=32, max_batch=8, read_batch=8,
                    slot_bytes=32)
    dp = DataPlane(cfg, mode="local", store=MemoryRoundStore())
    rng = np.random.default_rng(7)
    dp._host_ring[:] = rng.integers(0, 256, dp._host_ring.shape, np.uint8)
    S = cfg.slots
    want = np.concatenate([dp._host_ring[slot, (offset + i) % S]
                           for i in range(k)]).tobytes()
    got = dp._cache_rows(slot, offset, k)
    assert isinstance(got, bytes) and got == want
    # a written row: 4 B length, 4 B term, payload
    row = np.zeros(cfg.slot_bytes, np.uint8)
    row[0], row[8:11] = 3, list(b"abc")
    dp._host_ring[slot, offset % S] = row
    msgs, nxt = dp._decode_rows(dp._cache_rows(slot, offset, 1), offset, 1,
                                None)
    assert msgs == [b"abc"] and nxt == offset + 1


def test_mirror_gap_falls_back_to_device():
    """A resolve failure leaves a mirror gap; reads in it must come from
    the device ring (the authority), not serve stale mirror bytes."""
    cfg = small_cfg(partitions=1, slots=128, max_batch=8, read_batch=8)
    dp = _mk(cfg)
    try:
        sent = []
        for i in range(8):
            batch = [b"g-%03d-%d" % (i, j) for j in range(4)]
            sent.extend(batch)
            dp.submit_append(0, batch).result(timeout=30)
        # Simulate the gap: pretend rounds past row 16 never mirrored.
        with dp._lock:
            dp._cache_end[0] = 16
            dp._host_ring[0, 16:] = 0  # stale mirror bytes must not serve
        got, offset = [], 0
        while True:
            msgs, nxt = dp.read(0, offset, replica=0)
            if nxt == offset:
                break
            got.extend(msgs)
            offset = nxt
        assert got == sent
        assert dp.read_dispatches > 0, "gap reads must hit the device"
    finally:
        dp.stop()


def test_mirror_gap_heals_after_trim_passes():
    """A mirror gap must not disable the cache for the slot's lifetime:
    later rounds still write their rows physically, and once trim passes
    the post-gap run's base every unmirrored row is store-served — the
    cache heals and hot reads stop dispatching (r4 advisor: the old heal
    condition compared trim against each NEW round's base, which tracks
    the advancing log end and never fires)."""
    cfg = small_cfg(partitions=1, slots=128, max_batch=8, read_batch=8)
    dp = _mk(cfg)
    try:
        for i in range(4):
            dp.submit_append(
                0, [b"pre-%d-%d" % (i, j) for j in range(4)]
            ).result(timeout=30)
        with dp._lock:
            dp._cache_end[0] = 8  # simulate a resolve failure at row 8
        sent = []
        for i in range(60):
            batch = [b"heal-%03d-%d" % (i, j) for j in range(4)]
            sent.extend(batch)
            dp.submit_append(0, batch).result(timeout=30)
        with dp._lock:
            assert 0 not in dp._mirror_gap, "gap never healed"
            assert int(dp._cache_end[0]) == int(dp._log_end[0])
            trim = int(dp.trim[0])
        assert trim > 8, "test never advanced trim past the gap"
        # Hot reads (>= trim) are cache-served again, and serve the
        # right bytes.
        hits0, disp0 = dp.read_cache_hits, dp.read_dispatches
        got, offset = [], trim
        while True:
            msgs, nxt = dp.read(0, offset, replica=0)
            if nxt == offset:
                break
            got.extend(msgs)
            offset = nxt
        assert dp.read_dispatches == disp0, "healed reads still dispatched"
        assert dp.read_cache_hits > hits0
        assert got and got == sent[-len(got):]
    finally:
        dp.stop()


def test_mirror_seeded_by_recovery():
    """install() seeds the mirror from the replayed image: post-recovery
    hot reads are host-served immediately."""
    cfg = small_cfg(partitions=2, slots=64, max_batch=8, read_batch=8)
    store = MemoryRoundStore()
    dp = DataPlane(cfg, mode="local", store=store)
    dp.start()
    sent = []
    try:
        dp.set_leader(0, 0, 1)
        for i in range(6):
            batch = [b"r-%03d-%d" % (i, j) for j in range(8)]
            sent.extend(batch)
            dp.submit_append(0, batch).result(timeout=30)
    finally:
        dp.stop()
    image = replay_records(cfg, store.scan())
    dp2 = DataPlane(cfg, mode="local", store=MemoryRoundStore())
    dp2.install(image)
    dp2.start()
    try:
        got, offset = [], 0
        while True:
            msgs, nxt = dp2.read(0, offset, replica=0)
            if nxt == offset:
                break
            got.extend(msgs)
            offset = nxt
        assert got == sent
        assert dp2.read_dispatches == 0
    finally:
        dp2.stop()


def test_concurrent_producers_and_consumers_through_cache():
    """Writers mirror while readers drain: per-slot busy serialization
    plus the trim re-check must keep every consumer exact."""
    cfg = small_cfg(partitions=4, slots=64, max_batch=8, read_batch=8)
    dp = _mk(cfg)
    sent = {p: [] for p in range(4)}
    results: dict[int, list[bytes]] = {}
    try:
        def producer(p: int) -> None:
            for i in range(30):
                batch = [b"cc-%d-%03d-%d" % (p, i, j) for j in range(4)]
                sent[p].extend(batch)
                dp.submit_append(p, batch).result(timeout=30)

        def consumer(p: int) -> None:
            got, offset = [], 0
            deadline = time.monotonic() + 60
            while len(got) < 120 and time.monotonic() < deadline:
                msgs, nxt = dp.read(p, offset, replica=0)
                if nxt == offset:
                    time.sleep(0.001)  # tail poll: producer still working
                    continue
                got.extend(msgs)
                offset = nxt
            results[p] = got

        ps = [threading.Thread(target=producer, args=(p,)) for p in range(4)]
        cs = [threading.Thread(target=consumer, args=(p,)) for p in range(4)]
        for t in ps + cs:
            t.start()
        for t in ps:
            t.join()
        for t in cs:
            t.join()
        for p in range(4):
            assert results[p] == sent[p], f"partition {p} mismatch"
        assert dp.read_dispatches == 0
    finally:
        dp.stop()
