"""Client SDK end-to-end against an in-proc broker cluster.

This reproduces the reference's acceptance scenario (SURVEY.md §4: the
sample-producer → sample-consumer round trip over a multi-broker cluster,
BASELINE.json config #1), plus the client behaviors the reference
implements: RR spreading, cached metadata, auto-commit-after-read,
not-leader recovery.
"""

import time

import pytest

from ripplemq_tpu.client import ConsumerClient, ProducerClient
from ripplemq_tpu.client.selector import KeyedSelector, RoundRobinSelector
from ripplemq_tpu.metadata.models import Topic
from tests.broker_harness import InProcCluster, make_config


@pytest.fixture(scope="module")
def cluster():
    config = make_config(
        n_brokers=5,
        topics=(Topic("topic1", 3, 3), Topic("topic2", 2, 3)),
        metadata_election_timeout_s=0.6,
    )
    with InProcCluster(config) as c:
        c.wait_for_leaders()
        yield c


def bootstrap(cluster):
    return [b.address for b in cluster.config.brokers]


def make_producer(cluster, **kw):
    return ProducerClient(
        bootstrap(cluster),
        transport=cluster.client("producer"),
        metadata_refresh_s=0.5,
        **kw,
    )


def make_consumer(cluster, cid, **kw):
    return ConsumerClient(
        bootstrap(cluster),
        cid,
        transport=cluster.client(f"consumer-{cid}"),
        metadata_refresh_s=0.5,
        **kw,
    )


def test_sample_roundtrip(cluster):
    """The reference's sample apps: produce 2 messages, consume them back
    (sample-producer/Main.java:31-38, sample-consumer/Main.java:18-42)."""
    producer = make_producer(cluster)
    consumer = make_consumer(cluster, "sample-consumer")
    try:
        producer.produce("topic1", b"Message 1", partition=0)
        producer.produce("topic1", b"Message 2", partition=0)
        got = []
        for _ in range(8):  # poll until drained (storage rounds are padded)
            batch = consumer.consume("topic1", partition=0)
            if not batch and got:
                break
            got.extend(batch)
        assert got == [b"Message 1", b"Message 2"]
        # auto-commit happened: next consume returns nothing new
        assert consumer.consume("topic1", partition=0) == []
    finally:
        producer.close()
        consumer.close()


def test_round_robin_spreads_partitions(cluster):
    producer = make_producer(cluster)
    try:
        # topic2 has 2 partitions; 4 produces land 2 on each.
        offs = [producer.produce("topic2", f"rr{i}".encode()) for i in range(4)]
        t = producer._meta.topic("topic2")
        assert t.partitions == 2
        per_part = {}
        consumer = make_consumer(cluster, "rr-check", auto_commit=False)
        try:
            for pid in range(2):
                msgs = []
                offset = None
                while True:
                    got, _, off, nxt = consumer.consume_with_position(
                        "topic2", partition=pid, max_messages=100
                    )
                    if off == offset:
                        break
                    offset = off
                    msgs.extend(got)
                    consumer.commit("topic2", pid, nxt)
                per_part[pid] = [m for m in msgs if m.startswith(b"rr")]
        finally:
            consumer.close()
        assert len(per_part[0]) == 2 and len(per_part[1]) == 2
    finally:
        producer.close()


def test_produce_batch_single_rpc(cluster):
    producer = make_producer(cluster)
    try:
        base = producer.produce_batch(
            "topic1", [f"b{i}".encode() for i in range(40)], partition=1
        )
        assert base == 0
    finally:
        producer.close()


def test_manual_commit_at_least_once(cluster):
    producer = make_producer(cluster)
    consumer = make_consumer(cluster, "manual", auto_commit=False)
    try:
        producer.produce_batch("topic1", [b"x1", b"x2"], partition=2)
        msgs, pid, off, nxt = consumer.consume_with_position("topic1", partition=2)
        assert msgs == [b"x1", b"x2"]
        # Not committed: a re-read sees the same messages.
        again, _, _, _ = consumer.consume_with_position("topic1", partition=2)
        assert again == msgs
        consumer.commit("topic1", pid, nxt)  # commit next_offset, not off+n
        empty, _, _, _ = consumer.consume_with_position("topic1", partition=2)
        assert empty == []
    finally:
        producer.close()
        consumer.close()


def test_keyed_selector_stability(cluster):
    producer = make_producer(cluster, selector=KeyedSelector())
    try:
        t = producer._meta.topic("topic2")
        sel = KeyedSelector()
        p1 = sel.select(t, key=b"user-42")
        for _ in range(5):
            assert sel.select(t, key=b"user-42") == p1
    finally:
        producer.close()


def test_not_leader_recovery_after_failover():
    """Client keeps working when a partition leader dies mid-stream."""
    config = make_config(
        n_brokers=5,
        topics=(Topic("fo", 2, 3),),
        metadata_election_timeout_s=0.6,
    )
    with InProcCluster(config) as c:
        c.wait_for_leaders()
        producer = ProducerClient(
            [b.address for b in c.config.brokers],
            transport=c.client("fo-producer"),
            metadata_refresh_s=0.3,
            retries=20,
            retry_backoff_s=0.3,
            rpc_timeout_s=10.0,
        )
        try:
            assert producer.produce("fo", b"before", partition=0) == 0
            any_b = next(iter(c.brokers.values()))
            victim = any_b.manager.leader_of(("fo", 0))
            if victim == any_b.manager.current_controller():
                # The partition leader is ALSO the data-plane controller
                # (the common case: sticky assignment puts partition 0's
                # first replica on broker 0). Controller failover makes
                # this death survivable — wait for the standby set so a
                # promotion candidate holds the committed-round stream.
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if len(any_b.manager.current_standbys()) >= 1:
                        break
                    time.sleep(0.05)
                assert any_b.manager.current_standbys(), "no standbys formed"
            c.net.set_down(c.brokers[victim].addr)
            c.brokers[victim].stop()
            # The produce retry loop must ride out the failover window
            # (leader election — plus controller promotion in the
            # double-role case).
            off = producer.produce("fo", b"after", partition=0)
            assert off > 0  # storage offsets are ALIGN-padded per round
            # Readback proves both messages — through the real consumer
            # SDK (auto-commit paging, not_leader retries built in).
            consumer = ConsumerClient(
                [b.address for b in c.config.brokers],
                "fo-check",
                transport=c.client("fo-consumer"),
                metadata_refresh_s=0.3,
                retries=20,
                retry_backoff_s=0.3,
                rpc_timeout_s=10.0,
            )
            try:
                got = []
                deadline = time.monotonic() + 60
                while len(got) < 2 and time.monotonic() < deadline:
                    try:
                        got.extend(consumer.consume("fo", partition=0))
                    except Exception:
                        time.sleep(0.2)
                assert got == [b"before", b"after"], got
            finally:
                consumer.close()
        finally:
            producer.close()


def test_metadata_manager_survives_bootstrap_broker_loss(cluster):
    producer = make_producer(cluster)
    try:
        # All calls go through cached metadata even if one bootstrap addr
        # is down; fetch retries pick another random broker.
        down = cluster.config.brokers[-1].address
        cluster.net.set_down(down)
        try:
            for _ in range(5):
                producer._meta.refresh()
        finally:
            cluster.net.set_up(down)
    finally:
        producer.close()


def test_prefetch_round_robin_covers_all_partitions(cluster):
    """Prefetch mode must advance the round-robin selector ONCE per
    consume: the readahead probe and the sync fallback each advancing
    it desynchronized armed state from delivered partitions — with an
    even partition count the two paths alternated in lockstep and some
    partitions were never consumed at all (review finding)."""
    producer = make_producer(cluster)
    consumer = make_consumer(cluster, "prefetch-rr", prefetch=1,
                             max_messages=4)
    try:
        sent = {}
        for pid in range(2):  # topic2 has exactly 2 partitions
            sent[pid] = [b"rr-%d-%d" % (pid, i) for i in range(3)]
            for m in sent[pid]:
                producer.produce("topic2", m, partition=pid)
        want = set(sent[0]) | set(sent[1])
        got: set[bytes] = set()
        deadline = time.time() + 30
        while time.time() < deadline and not want <= got:
            # The module-shared cluster holds other tests' messages too
            # (fresh consumer id reads from offset 0): filter to ours.
            got |= {m for m in consumer.consume("topic2")
                    if m.startswith(b"rr-")}
        assert want <= got, got
        consumer.flush_commits()
    finally:
        producer.close()
        consumer.close()


class _HeldCommits:
    """Transport proxy: `offset.commit` / `offset.commit.multi` requests
    sent with call_async are
    HELD until the test lets them reach the broker, in the order the
    test chooses — a broker's worker pool may run two requests of one
    connection in either order. Everything else goes straight through."""

    def __init__(self, inner) -> None:
        self._inner = inner
        self.held: list = []  # (addr, request, future)

    def call(self, addr, request, timeout=3.0):
        return self._inner.call(addr, request, timeout=timeout)

    def call_async(self, addr, request):
        if request.get("type") not in ("offset.commit",
                                       "offset.commit.multi"):
            return self._inner.call_async(addr, request)
        from concurrent.futures import Future

        fut: Future = Future()
        self.held.append((addr, request, fut))
        return fut

    def land(self, i: int) -> None:
        addr, request, fut = self.held[i]
        fut.set_result(self._inner.call(addr, request))

    def close(self) -> None:
        self._inner.close()


def test_pipelined_commits_never_move_the_position_back(cluster):
    """FAILING-BEFORE (seen on the chip at 130k msgs/s, PR 28): with a
    commit slower than the poll interval the readahead consumer had
    several auto-commits of ONE partition in flight; the broker ran them
    out of order, the older one landed last, the committed position
    moved back, and the next fetch without an explicit offset delivered
    a window twice. Now one commit request is in flight per leader (PR
    40: an offset.commit.multi for every partition polled there) and the
    newest offsets wait behind it."""
    producer = make_producer(cluster)
    transport = _HeldCommits(cluster.client("consumer-held"))
    consumer = ConsumerClient(bootstrap(cluster), "held-commits",
                              transport=transport, metadata_refresh_s=0.5,
                              prefetch=1, max_messages=2)
    try:
        sent = [b"held-%d" % i for i in range(6)]
        for m in sent:
            producer.produce("topic2", m, partition=1)
        got: list[bytes] = []
        # The module-shared cluster may hold other tests' messages on
        # this partition: a fresh consumer id reads them first.
        deadline = time.time() + 30
        while time.time() < deadline and len(
                [m for m in got if m.startswith(b"held-")]) < len(sent):
            got += consumer.consume("topic2", partition=1)
        ours = [m for m in got if m.startswith(b"held-")]
        assert ours == sent
        # Three or more windows were delivered and not one commit has
        # landed: exactly ONE went out, the newest offset is parked.
        assert len(transport.held) == 1
        # The old client had sent one per window; landing them newest
        # first is what moved the position back. Here there is nothing
        # to reorder: land the one, then the flush commits the parked
        # offset behind it.
        transport.land(0)
        consumer.flush_commits()
        assert len(transport.held) == 1  # the parked one went out sync
        # A fetch without an explicit offset (what follows an empty
        # window) starts past everything delivered: nothing comes twice.
        more = [b"held-more-%d" % i for i in range(2)]
        for m in more:
            producer.produce("topic2", m, partition=1)
        again: list[bytes] = []
        deadline = time.time() + 30
        while time.time() < deadline and len(again) < len(more):
            again += consumer.consume("topic2", partition=1)
        assert again == more
        for i in range(1, len(transport.held)):
            transport.land(i)  # close() flushes without waiting one out
    finally:
        producer.close()
        consumer.close()
