"""The keyed, batching producer (`ProducerClient.send`) and the
produce.multi request, against a plain reference.

The reference is a dict of per-partition lists and a per-key list. It
imports nothing of the broker and is fed the same seeded keyed stream the
cluster is: (a) every acked message is read back byte-exact from its
key's partition, once, on every replica; (b) one key's messages appear in
send order; (c) a request whose parts meet different fates acks each on
its own and the retried part commits once; (d) a replayed request commits
nothing twice; (e) the accumulator's rules on a fake clock.
"""

import os
import time

import numpy as np
import pytest

from ripplemq_tpu.client import ProducerClient
from ripplemq_tpu.client.accumulator import Accumulator
from ripplemq_tpu.metadata.models import Topic
from ripplemq_tpu.wire.transport import RpcTimeout, Transport
from tests.broker_harness import InProcCluster, make_config
from tests.helpers import wait_until

ONE, MANY = "one", "many"   # a 1-partition and a 12-partition topic


class Reference:
    """What the logs must hold, from the sender's side alone."""

    def __init__(self) -> None:
        self.by_partition: dict[int, list[tuple[int, bytes]]] = {}
        self.by_key: dict[bytes, list[bytes]] = {}

    def acked(self, key: bytes, payload: bytes, partition: int,
              offset: int) -> None:
        self.by_partition.setdefault(partition, []).append((offset, payload))
        self.by_key.setdefault(key, []).append(payload)

    def log(self, partition: int) -> list[bytes]:
        return [m for _, m in sorted(self.by_partition.get(partition, []))]


def keyed_stream(seed: int, n: int, n_keys: int = 97):
    """(key, payload) pairs: a few hot keys, a long tail; payloads are
    distinct and name their key, so order within a key is readable."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n_keys + 1) ** 0.99
    ranks = rng.choice(n_keys, size=n, p=w / w.sum())
    perm = rng.permutation(n_keys)
    return [(b"k%03d" % perm[r], b"%05d/k%03d" % (i, perm[r]))
            for i, r in enumerate(ranks)]


def bootstrap(cluster):
    return [b.address for b in cluster.config.brokers]


def make_producer(cluster, name="keyed-p", transport=None, **kw):
    kw.setdefault("metadata_refresh_s", 0.5)
    return ProducerClient(bootstrap(cluster),
                          transport=transport or cluster.client(name), **kw)


def read_log(cluster, topic: str, partition: int, name: str = "rd"
             ) -> list[bytes]:
    """The partition from offset 0 to its settled end, through the
    leader's consume RPC with explicit offsets (no committed offset
    moves, so one consumer name serves every read of the module)."""
    rpc = cluster.client(name)
    out: list[bytes] = []
    offset, idle = 0, 0
    while idle < 3:
        addr = cluster.broker_addr(
            cluster.leader_broker(topic, partition).broker_id)
        resp = rpc.call(addr, {"type": "consume", "topic": topic,
                               "partition": partition, "consumer": "rd",
                               "offset": offset, "max_messages": 64},
                        timeout=10.0)
        assert resp.get("ok"), resp
        out.extend(bytes(m) for m in resp["messages"])
        idle = 0 if resp["messages"] else idle + 1
        offset = int(resp["next_offset"])
    return out


def send_all(producer, topic, stream, ref: Reference) -> None:
    waiters = [(k, m, producer.send(topic, m, k)) for k, m in stream]
    for k, m, w in waiters:
        offset = w(30.0)
        ref.acked(k, m, w.partition, offset)


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    config = make_config(
        n_brokers=3, topics=(Topic(ONE, 1, 3), Topic(MANY, 12, 3)),
        metadata_election_timeout_s=0.6,
    )
    with InProcCluster(config,
                       data_dir=tmp_path_factory.mktemp("keyed")) as c:
        c.wait_for_leaders()
        yield c


@pytest.fixture(scope="module")
def produced(cluster):
    """Both topics written once through send(), two producers each (a
    key's order is per producer); the replica dirs are read after the
    cluster has stopped, by the last test of the module."""
    out = {}
    for topic in (ONE, MANY):
        ref = Reference()
        runs = []
        for who in range(2):
            p = make_producer(cluster, f"p-{topic}-{who}", linger_s=0.002)
            stream = [(k, b"%d:" % who + m)
                      for k, m in keyed_stream(11 + who, 300)]
            send_all(p, topic, stream, ref)
            runs.append((p, stream))
        for p, _ in runs:
            p.close()
        out[topic] = (ref, runs)
    return out


@pytest.mark.parametrize("topic,partitions", [(ONE, 1), (MANY, 12)])
def test_acked_messages_read_back_once_in_key_order(cluster, produced,
                                                    topic, partitions):
    """(a) delivered side and (b): byte-exact, once, in the key's
    partition, each key in send order per producer."""
    ref, runs = produced[topic]
    assert sum(len(v) for v in ref.by_partition.values()) == 600
    where = {}
    for p in range(partitions):
        got = read_log(cluster, topic, p, f"rd-{topic}-{p}")
        assert got == ref.log(p), f"{topic}/{p} differs from the reference"
        for i, m in enumerate(got):
            assert m not in where, "a message was delivered twice"
            where[m] = (p, i)
    assert len(where) == 600
    for key, sent in ref.by_key.items():
        homes = {where[m][0] for m in sent}
        assert len(homes) == 1, f"key {key!r} landed in {homes}"
        for who in (b"0:", b"1:"):
            at = [where[m][1] for m in sent if m.startswith(who)]
            assert at == sorted(at), f"key {key!r} out of send order"
    if partitions > 1:
        assert len(ref.by_partition) > partitions // 2  # keys spread


def test_one_request_carries_many_partitions(cluster, produced):
    """The point of produce.multi: fewer requests than parts, fewer
    parts than messages."""
    ctl = cluster.brokers[cluster.controller_id()]
    counters = {}
    for b in cluster.brokers.values():
        for k, v in b.dispatch({"type": "admin.metrics"})["metrics"][
                "counters"].items():
            counters[k] = counters.get(k, 0) + v
    assert 0 < counters["produce.multi_requests"] \
        < counters["produce.multi_parts"] < 1200
    m = ctl.dispatch({"type": "admin.metrics"})["metrics"]
    assert m["counters"]["round.staged_rows"] > 0
    assert m["histograms"]["round.active_slots"]["max"] >= 2


def test_part_answers_are_independent_and_admission_holds(cluster):
    """A raw produce.multi: a good part, a part for a partition this
    broker does not know, a part whose keys span beyond the partition's
    range, an empty part - four answers, one commit."""
    topic = MANY
    leader = cluster.leader_broker(topic, 3)
    addr = cluster.broker_addr(leader.broker_id)
    a = {x.partition_id: x for x in cluster.topic_view(topic)}[3]
    raw = cluster.client("raw-multi")
    resp = raw.call(addr, {"type": "produce.multi", "producer": "raw/x",
                           "parts": [
        {"topic": topic, "partition": 3, "messages": [b"raw-ok"],
         "pgen": a.generation, "key_span": [a.range_lo, a.range_hi - 1]},
        {"topic": topic, "partition": 99, "messages": [b"raw-unknown"]},
        {"topic": topic, "partition": 3, "messages": [b"raw-span"],
         "pgen": a.generation, "key_span": [a.range_lo, a.range_hi]},
        {"topic": topic, "partition": 3, "messages": []},
    ]}, timeout=10.0)
    assert resp["ok"] and len(resp["parts"]) == 4
    ok, unknown, span, empty = resp["parts"]
    assert ok["ok"] and ok["count"] == 1
    assert unknown["error"].startswith("unknown_partition")
    assert span["error"].startswith("stale_partition_gen:")
    assert any(d["partition_id"] == 3 for d in span["routing"])
    assert empty["error"].startswith("bad_request")
    got = read_log(cluster, topic, 3, "rd-raw")
    assert got.count(b"raw-ok") == 1
    assert b"raw-span" not in got and b"raw-unknown" not in got


class DropFirstResponse(Transport):
    """Delivers every request; the FIRST produce.multi's response is
    lost on the way back (the sender sees a timeout, the broker
    committed)."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.dropped = 0

    def call(self, addr, request, timeout=3.0):
        resp = self.inner.call(addr, request, timeout)
        if request.get("type") == "produce.multi" and not self.dropped:
            self.dropped += 1
            raise RpcTimeout(f"{addr}: response lost (injected)")
        return resp


@pytest.mark.parametrize("topic", [ONE, MANY])
def test_replayed_request_commits_nothing_twice(cluster, topic):
    """(d) the response is dropped, the parts are replayed under their
    reserved (pid, seq), the broker's dedup table answers with the
    original offsets."""
    transport = DropFirstResponse(cluster.client(f"replay-{topic}"))
    p = make_producer(cluster, transport=transport, linger_s=0.05,
                      retry_backoff_s=0.01)
    ref = Reference()
    stream = [(k, b"r:" + m) for k, m in keyed_stream(23, 40)]
    try:
        send_all(p, topic, stream, ref)
    finally:
        p.close()
    assert transport.dropped == 1
    mine = []
    for part in range(1 if topic == ONE else 12):
        got = [m for m in read_log(cluster, topic, part,
                                   f"rd-replay-{topic}-{part}")
               if m.startswith(b"r:")]
        assert got == [m for m in ref.log(part) if m.startswith(b"r:")]
        mine += got
    assert sorted(mine) == sorted(m for _, m in stream)  # each exactly once


def test_split_fence_refuses_one_part_and_the_retry_commits_once():
    """(c) after a split the producer's routing is stale for the parent:
    that part draws stale_partition_gen, the other partition's part of
    the same request is acked, and the refused part, re-split from the
    refusal's routing payload, commits once, in order."""
    topic = "el"
    config = make_config(3, topics=(Topic(topic, 2, 3),), spare_slots=1,
                         split_handoff_timeout_s=5.0)
    with InProcCluster(config) as cluster:
        cluster.wait_for_leaders()
        p = make_producer(cluster, "el-p", metadata_refresh_s=3600.0,
                          linger_s=0.05, retry_backoff_s=0.01)
        ref = Reference()
        try:
            send_all(p, topic, keyed_stream(31, 60), ref)
            gen0 = cluster.topic_view(topic)[0].generation
            r = cluster.admin_split(topic, 0)
            assert r.get("ok"), r
            child = int(r["child"])
            assert wait_until(
                lambda: all(a.state == "active"
                            for a in cluster.topic_view(topic)),
                timeout=20.0), "handoff window never cut over"
            view = {a.partition_id: a for a in cluster.topic_view(topic)}

            # The raw wire first: one request, two fates.
            addr = cluster.broker_addr(
                cluster.leader_broker(topic, 0).broker_id)
            resp = cluster.client("el-raw").call(addr, {
                "type": "produce.multi", "producer": "raw/el", "parts": [
                    {"topic": topic, "partition": 0, "messages": [b"stale"],
                     "pgen": gen0, "key_span": [0, 1]},
                    {"topic": topic, "partition": 0, "messages": [b"fresh"],
                     "pgen": view[0].generation, "key_span": [0, 1]},
                ]}, timeout=10.0)
            stale, fresh = resp["parts"]
            assert stale["error"].startswith("stale_partition_gen:")
            assert stale["generation"] == view[0].generation
            assert fresh["ok"]

            # The client, with routing from before the split.
            before = dict(ref.by_partition)
            stream2 = [(k, b"2:" + m) for k, m in keyed_stream(32, 120)]
            send_all(p, topic, stream2, ref)
            landed = {part for part in ref.by_partition
                      if len(ref.by_partition[part])
                      > len(before.get(part, []))}
            assert child in landed, f"nothing was rerouted to {child}"
        finally:
            p.close()
        where = {}
        for part in (0, 1, child):
            got = [m for m in read_log(cluster, topic, part, f"rd-el-{part}")
                   if m not in (b"fresh",)]
            assert got == ref.log(part)
            for i, m in enumerate(got):
                assert m not in where
                where[m] = (part, i)
        assert len(where) == 180
        for key, sent in ref.by_key.items():
            second = [where[m] for m in sent if m.startswith(b"2:")]
            assert len({part for part, _ in second}) <= 1
            assert second == sorted(second), f"key {key!r} out of order"


def test_every_replica_holds_every_acked_message(cluster, produced):
    """(a) the replica side: after a clean stop, each broker's data dir
    holds each partition's acked messages byte-exact, once. LAST in the
    module: it stops the cluster."""
    from ripplemq_tpu.storage.segment import REC_APPEND, scan_store

    slots = {(topic, p): cluster.brokers[0].manager.slot_of((topic, p))
             for topic, n in ((ONE, 1), (MANY, 12)) for p in range(n)}
    sb = cluster.config.engine.slot_bytes
    root = cluster._data_dir
    cluster.stop()
    dirs = [os.path.join(str(root), f"broker-{i}", "segments")
            for i in cluster.brokers]
    assert len(dirs) == 3
    for d in dirs:
        rows: dict[int, dict[int, bytes]] = {}
        for rec_type, slot, base, body in scan_store(d):
            if rec_type == REC_APPEND:
                rows.setdefault(slot, {})[base] = bytes(body)
        for (topic, p), slot in slots.items():
            held = []
            for base in sorted(rows.get(slot, {})):
                block = np.frombuffer(rows[slot][base], np.uint8
                                      ).reshape(-1, sb)
                lens = block[:, :4].copy().view("<i4")[:, 0]
                held += [block[i, 8:8 + n].tobytes()
                         for i, n in enumerate(lens) if n > 0]
            want = produced[topic][0].log(p)
            mine = [m for m in held if m[:2] in (b"0:", b"1:")]
            assert mine == want, f"{d}: {topic}/{p} differs"


# ------------------------------------------------ (e) the accumulator


class Clock:
    def __init__(self) -> None:
        self.now = 100.0


LEADER = {("t", 0): "a:1", ("t", 1): "a:1", ("t", 2): "b:1"}


def leader_of(topic, partition):
    return LEADER.get((topic, partition))


def fill(acc, clock, partition, n, size=10):
    return [acc.append("t", partition, b"x" * size, partition, clock.now)
            for _ in range(n)]


def test_accumulator_linger():
    acc, clock = Accumulator(linger_s=0.010, max_in_flight=5), Clock()
    fill(acc, clock, 0, 3)
    out, wake, lost = acc.drain(clock.now, leader_of)
    assert out == {} and wake == pytest.approx(100.010) and not lost
    clock.now += 0.004
    fill(acc, clock, 1, 1)          # younger: not ready with the older
    clock.now = 100.010
    out, wake, _ = acc.drain(clock.now, leader_of)
    assert [p.partition for p in out["a:1"]] == [0]
    assert len(out["a:1"][0].messages) == 3
    assert wake == pytest.approx(100.014)
    clock.now = 100.015
    out, wake, _ = acc.drain(clock.now, leader_of)
    assert [p.partition for p in out["a:1"]] == [1] and wake is None


@pytest.mark.parametrize("how", ["rows", "bytes", "flush"])
def test_accumulator_full_batch_leaves_at_once(how):
    clock = Clock()
    acc = Accumulator(linger_s=10.0, batch_size=40 if how == "bytes"
                      else 1 << 20,
                      max_rows=lambda: 4 if how == "rows" else None)
    got = fill(acc, clock, 0, 5)
    if how == "flush":
        assert acc.drain(clock.now, leader_of)[0] == {}
        acc.flushing = True
        out, _, _ = acc.drain(clock.now, leader_of)
        assert len(out["a:1"][0].messages) == 5
        return
    assert [idx for _, idx, _ in got] == [0, 1, 2, 3, 0]  # a second part
    assert got[3][2] and got[4][2]      # filled, then opened: look again
    out, wake, _ = acc.drain(clock.now, leader_of)
    assert len(out["a:1"]) == 1 and len(out["a:1"][0].messages) == 4
    assert out["a:1"][0].full and wake is None  # the rest waits its turn


def test_accumulator_one_part_in_flight_per_partition():
    acc, clock = Accumulator(linger_s=0.0, max_rows=lambda: 2), Clock()
    fill(acc, clock, 0, 5)              # parts of 2, 2, 1
    first = acc.drain(clock.now, leader_of)[0]["a:1"]
    assert len(first) == 1 and first[0].sent
    assert acc.drain(clock.now, leader_of)[0] == {}     # muted
    acc.request_done("a:1")
    acc.retry(first[0], not_before=clock.now + 1.0)
    assert acc.drain(clock.now, leader_of)[0] == {}     # backing off,
    clock.now += 1.0                                    # and nothing passes
    again = acc.drain(clock.now, leader_of)[0]["a:1"]
    assert again == first                               # the same part
    acc.request_done("a:1")
    acc.complete(first[0])
    second = acc.drain(clock.now, leader_of)[0]["a:1"]
    assert second[0] is not first[0] and len(second[0].messages) == 2
    assert acc.pending() == 2


def test_accumulator_max_in_flight_per_leader():
    acc, clock = Accumulator(linger_s=0.0, max_in_flight=2), Clock()
    sent = []
    for k in range(2):
        fill(acc, clock, k, 1)
        sent += acc.drain(clock.now, leader_of)[0]["a:1"]
    assert acc.saturated()
    fill(acc, clock, 0, 1)              # partition 0 is muted anyway
    fill(acc, clock, 2, 1)              # another leader: goes
    out = acc.drain(clock.now, leader_of)[0]
    assert list(out) == ["b:1"] and not acc.saturated()
    acc.request_done("a:1")
    acc.complete(sent[1])
    fill(acc, clock, 1, 1)
    out = acc.drain(clock.now, leader_of)[0]
    assert [p.partition for p in out["a:1"]] == [1]  # one request, one slot
    fill(acc, clock, 3, 1)              # no leader known
    out, wake, lost = acc.drain(clock.now, leader_of)
    assert out == {} and lost


# ------------------------------------- the shared pieces this path changed


@pytest.mark.parametrize("shape", ["static-1024", "split", "merged"])
def test_route_key_bisection_matches_the_scan(shape):
    """`MetadataManager.route_key` by bisection answers what the old scan
    over the assignments answered, for every hash of the range space:
    static ranges, a split child appended out of range order, and a
    retired child whose empty range must never win."""
    import dataclasses

    from ripplemq_tpu.client.metadata import MetadataManager
    from ripplemq_tpu.metadata.models import (
        RANGE_SPACE, PartitionAssignment, Topic as T)

    n = 1024 if shape == "static-1024" else 4
    assigns = [PartitionAssignment(
        p, (0, 1, 2), range_lo=RANGE_SPACE * p // n,
        range_hi=RANGE_SPACE * (p + 1) // n) for p in range(n)]
    if shape != "static-1024":
        a = assigns[1]
        mid = (a.range_lo + a.range_hi) // 2
        assigns[1] = dataclasses.replace(a, range_hi=mid, generation=1)
        assigns.append(PartitionAssignment(
            n, (0, 1, 2), generation=1, range_lo=mid, range_hi=a.range_hi,
            origin=1))
    if shape == "merged":
        child = assigns[-1]
        assigns[1] = dataclasses.replace(assigns[1], generation=2,
                                         range_hi=child.range_hi)
        assigns[-1] = dataclasses.replace(
            child, generation=2, range_lo=child.range_hi, state="retired")
    meta = MetadataManager(Transport(), ["x:1"])
    meta._topics = {"t": T("t", len(assigns), 3, tuple(assigns))}
    step = 1 if shape != "static-1024" else 7
    for h in range(0, RANGE_SPACE, step):
        scan = next(a.partition_id for a in assigns
                    if a.state != "retired" and a.owns_key(h))
        assert meta.route_key("t", h) == scan, h
    assert meta.route_key("t", RANGE_SPACE + 5) == meta.route_key("t", 5)
    assert meta.route_key("nope", 1) is None
    t = meta._topics["t"]
    assert all(t.assignment_for(a.partition_id) is a for a in assigns)
    assert t.assignment_for(len(assigns) + 3) is None


def test_submit_appends_is_submit_append_for_many():
    """One lock hold for a request's parts, the same outcome each: good
    batches commit in order, a bad one fails alone, a replayed (pid, seq)
    is answered with its original offset."""
    from ripplemq_tpu.broker.dataplane import DataPlane
    from tests.helpers import small_cfg

    dp = DataPlane(small_cfg(), mode="local", max_retry_rounds=3)
    dp.start()
    try:
        for slot in (0, 1):
            dp.set_leader(slot, 0, 1)
        futs = dp.submit_appends([
            (0, [b"a0", b"a1"], 7, 0, None),
            (1, [b"b0"], 7, 0, None),
            (0, [b""], 0, -1, None),             # empty message: refused
            (99, [b"x"], 0, -1, None),           # no such slot
            (0, [b"a2"], 7, 2, None),
        ])
        assert futs[0].result(10) == 0 and futs[1].result(10) == 0
        assert futs[4].result(10) in (2, 8)
        for bad in (futs[2], futs[3]):
            with pytest.raises(ValueError):
                bad.result(10)
        again = dp.submit_appends([(0, [b"a0", b"a1"], 7, 0, None),
                                   (1, [b"b1"], 7, 1, None)])
        assert again[0].result(10) == 0          # deduped, not re-appended
        assert again[1].result(10) > 0
        got, _ = dp.read(0, 0, replica=0)
        assert [bytes(m) for m in got][:2] == [b"a0", b"a1"]
        assert dp.submit_appends([]) == []
    finally:
        dp.stop()


def test_retry_run_next_delay_is_attempt_without_the_sleep():
    import random

    from ripplemq_tpu.wire.retry import RetryPolicy

    slept = []
    make = lambda: RetryPolicy(  # noqa: E731
        max_attempts=3, base_backoff_s=0.1, jitter=0.5,
        sleep=slept.append, rng=random.Random(4))
    a, b = make().begin(), make().begin()
    delays = [b.next_delay() for _ in range(4)]
    assert [a.attempt() for _ in range(4)] == [True, True, True, False]
    assert delays[0] == 0.0 and delays[3] is None
    assert slept == delays[1:3] and a.sleeps == slept
    assert 0.05 <= delays[1] <= 0.1 < delays[2] <= 0.2


class HeldTransport(Transport):
    """One leader, four partitions; produce.multi futures stay unresolved
    until the test answers them, so the sender's timing shows."""

    def __init__(self) -> None:
        self.sent: list[tuple[dict, object]] = []

    def call(self, addr, request, timeout=3.0):
        from ripplemq_tpu.metadata.models import (
            RANGE_SPACE, PartitionAssignment, Topic as T)

        if request["type"] == "meta.topics":
            t = T("t", 4, 1, tuple(PartitionAssignment(
                p, (0,), leader=0, range_lo=RANGE_SPACE * p // 4,
                range_hi=RANGE_SPACE * (p + 1) // 4) for p in range(4)))
            return {"ok": True, "topics": [t.to_dict()], "max_batch": 8,
                    "brokers": [{"broker_id": 0, "host": "h", "port": 1}]}
        if request["type"] == "producer.register":
            return {"ok": True, "pid": 5}
        raise AssertionError(request["type"])

    def call_async(self, addr, request):
        from concurrent.futures import Future

        fut = Future()
        self.sent.append((request, fut))
        return fut

    def answer(self, i: int, base: int = 0) -> None:
        req, fut = self.sent[i]
        fut.set_result({"ok": True, "parts": [
            {"ok": True, "base_offset": base, "count": len(p["messages"])}
            for p in req["parts"]]})


def key_for(producer, partition: int) -> bytes:
    return next(k for k in (b"k%d" % i for i in range(1000))
                if producer.partition_for("t", k) == partition)


def test_sender_thread_keeps_the_accumulators_rules_under_real_time():
    tr = HeldTransport()
    p = ProducerClient(["h:1"], transport=tr, linger_s=0.005,
                       max_in_flight=2, metadata_refresh_s=3600.0)
    try:
        k0, k1, k2 = (key_for(p, n) for n in (0, 1, 2))
        w0 = p.send("t", b"a", k0)
        assert wait_until(lambda: len(tr.sent) == 1, timeout=2.0)
        # A part that opens while a request is out leaves after ITS
        # linger, not when that request's response comes.
        w1 = p.send("t", b"b", k1)
        assert wait_until(lambda: len(tr.sent) == 2, timeout=2.0)
        assert w1.sent() and not w1.done()
        # Partition 0 is muted and the leader's window (2) is full.
        w0b, w2 = p.send("t", b"c", k0), p.send("t", b"d", k2)
        time.sleep(0.05)
        assert len(tr.sent) == 2 and not w2.sent()
        tr.answer(1, base=40)           # a slot frees: partition 2 goes,
        assert w1(2.0) == 40            # partition 0 stays muted
        assert wait_until(lambda: len(tr.sent) == 3, timeout=2.0)
        assert [x["partition"] for x in tr.sent[2][0]["parts"]] == [2]
        tr.answer(0, base=8)
        assert w0(2.0) == 8
        assert wait_until(lambda: len(tr.sent) == 4, timeout=2.0)
        part = tr.sent[3][0]["parts"][0]
        assert part["partition"] == 0 and part["seq"] == 1
        assert part["key_span"][0] == part["key_span"][1]
        assert tr.sent[3][0]["pid"] == 5
        tr.answer(2, base=16)
        tr.answer(3, base=24)
        assert (w2(2.0), w0b(2.0)) == (16, 24)
        assert w0b.partition == 0 and w0b.index == 0 and w0b.acked_ns > 0
    finally:
        p.close(timeout=1.0)
