"""A readahead consumer's session with a leader (PR 40): one
`consume.multi` and one `offset.commit.multi` carry every partition the
caller polls there, against a strict (`prefetch` 0) client reading the
same logs through the single-partition requests.

In-process cluster as tests/test_client.py builds it. The session's
clock is the test's, so "polled within `_ANSWER_MAX_AGE_S`" and "idle
for `_SESSION_IDLE_S`" are exact.
"""

from concurrent.futures import Future

import pytest

from ripplemq_tpu.chaos.cluster import small_engine
from ripplemq_tpu.client import ConsumerClient, ProducerClient
from ripplemq_tpu.client import consumer as consumer_mod
from ripplemq_tpu.metadata.models import Topic
from ripplemq_tpu.wire.retry import RetryPolicy
from ripplemq_tpu.wire.transport import RpcError
from tests.broker_harness import InProcCluster, make_config

WIDE = "wide"  # 128 partitions, RF 3, over four brokers: leaders sit on
#                the controller wherever it holds a replica, so a
#                quarter of them are led by a broker that is not it


@pytest.fixture(scope="module")
def cluster():
    topics = (Topic(WIDE, 128, 3),)
    config = make_config(
        n_brokers=4, topics=topics,
        engine=small_engine(partitions=128, replicas=3, max_consumers=64),
        metadata_election_timeout_s=0.6,
    )
    with InProcCluster(config) as c:
        c.wait_for_leaders()
        yield c


class Clock:
    def __init__(self) -> None:
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float = 0.001) -> None:
        self.t += dt


_taken: set[int] = set()


def take(cluster, n: int, controller: bool = True) -> list[int]:
    """`n` partitions no other test of the module uses, all led by one
    broker: the controller, or one that is not."""
    ctl = cluster.controller_id()
    by_leader: dict[int, list[int]] = {}
    for p in range(128):
        if p not in _taken:
            by_leader.setdefault(
                cluster.leader_of_key(WIDE, p), []).append(p)
    pick = [ps for b, ps in sorted(by_leader.items())
            if (b == ctl) == controller and len(ps) >= n]
    assert pick, (n, controller, {b: len(v) for b, v in by_leader.items()})
    _taken.update(pick[0][:n])
    return pick[0][:n]


def bootstrap(cluster):
    return [b.address for b in cluster.config.brokers]


def make_consumer(cluster, cid, transport=None, **kw):
    c = ConsumerClient(bootstrap(cluster), cid,
                       transport=transport or cluster.client(f"c-{cid}"),
                       metadata_refresh_s=0.5, **kw)
    c._clock = Clock()
    return c


def fill(cluster, parts, tag: bytes, n: int = 5) -> dict[int, list[bytes]]:
    producer = ProducerClient(bootstrap(cluster),
                              transport=cluster.client("p"),
                              metadata_refresh_s=0.5)
    try:
        sent = {p: [b"%s-%d-%d" % (tag, p, i) for i in range(n)]
                for p in parts}
        for p, msgs in sent.items():
            producer.produce_batch(WIDE, msgs, partition=p)
        return sent
    finally:
        producer.close()


def counters(cluster) -> dict[str, int]:
    out: dict[str, int] = {}
    for b in cluster.brokers.values():
        for k, v in b.metrics.snapshot()["counters"].items():
            out[k] = out.get(k, 0) + v
    return out


def delta(after: dict, before: dict, name: str) -> int:
    return after.get(name, 0) - before.get(name, 0)


def rotate(consumer, parts, rotations=None) -> tuple[dict, int]:
    """Poll `parts` in turn, 1 ms apart, until a whole rotation comes
    back empty (or `rotations` times); what each partition delivered, as
    a list of windows, and the rotations made."""
    got = {p: [] for p in parts}
    made = 0
    while rotations is None or made < rotations:
        any_msgs = False
        for p in parts:
            consumer._clock.tick()
            msgs = consumer.consume(WIDE, partition=p)
            if msgs:
                got[p].append(msgs)
                any_msgs = True
        made += 1
        if rotations is None and not any_msgs:
            break
    return got, made


def flat(windows: list) -> list[bytes]:
    return [m for w in windows for m in w]


def committed(cluster, cid: str, p: int) -> int:
    ctl = cluster.brokers[cluster.controller_id()]
    return ctl.dataplane.read_offset(
        ctl.manager.slot_of((WIDE, p)), ctl.manager.consumer_slot(cid))


# -- (a) the same bytes in the same order, one request a rotation ---------


@pytest.mark.parametrize("n", [1, 2, 32])
def test_session_delivers_what_a_strict_client_does(cluster, n):
    parts = take(cluster, n)
    sent = fill(cluster, parts, b"a%d" % n)
    strict = make_consumer(cluster, f"strict-a{n}", max_messages=2)
    ra = make_consumer(cluster, f"ra-a{n}", prefetch=1, max_messages=2)
    try:
        want, _ = rotate(strict, parts)
        before = counters(cluster)
        got, made = rotate(ra, parts)
        after = counters(cluster)
        for p in parts:
            assert flat(got[p]) == flat(want[p]) == sent[p]
            # max_messages cut every part: 5 messages come as 2, 2, 1
            assert [len(w) for w in got[p]] == [2, 2, 1]
        # The first rotation learns the partitions, a request each;
        # every later one is ONE consume.multi of n parts.
        assert delta(after, before, "consume.multi_requests") \
            == n + (made - 1)
        assert delta(after, before, "consume.multi_parts") \
            == n + (made - 1) * n
        assert delta(after, before, "commit.multi_requests") >= 1
        ra.flush_commits()
        for p in parts:
            assert committed(cluster, f"ra-a{n}", p) \
                == committed(cluster, f"strict-a{n}", p)
    finally:
        strict.close()
        ra.close()


def test_a_fetch_carries_only_what_the_caller_asks_for_soon(cluster):
    """Partitions polled 40 ms apart: the one asked for and the one
    40 ms behind it ride a request, the one 80 ms behind does not - its
    answer would be older than `_ANSWER_MAX_AGE_S` when handed out."""
    parts = take(cluster, 3)
    ra = make_consumer(cluster, "ra-age", prefetch=1)
    try:
        before = counters(cluster)
        for _ in range(4):
            for p in parts:
                ra._clock.tick(0.04)
                ra.consume(WIDE, partition=p)
        after = counters(cluster)
        # rotation 1: three requests of one part; then p0+p1, p2+p0,
        # p1+p2, ... : every second poll is answered from memory.
        assert delta(after, before, "consume.multi_parts") \
            == 2 * delta(after, before, "consume.multi_requests") - 3
        assert delta(after, before, "consume.multi_requests") < 12
    finally:
        ra.close()


# -- the caller's cadence is counted on the caller's clock (PR 45) --------


class Slow:
    """Transport proxy: a synchronous request HOLDS its caller - the
    consumer's clock moves by `cost(request)` seconds while it is out -
    and `held` is the sum (a retry policy's back-off sleeps through
    `hold` too), so the test keeps the caller's clock by its own count. Every consume.multi is noted as (partition asked for
    first, all partitions listed). `refuse` names a partition whose part
    the next consume.multi listing it answers `not_leader` (once);
    `fail` makes the next single-partition consume raise (once)."""

    def __init__(self, inner, clock, cost) -> None:
        self._inner, self._clock, self._cost = inner, clock, cost
        self.held = 0.0
        self.fetches: list[list[int]] = []
        self.singles = 0
        self.refuse = None
        self.fail = False

    def hold(self, dt: float) -> None:
        self._clock.tick(dt)
        self.held += dt

    def call(self, addr, request, timeout=3.0):
        self.hold(self._cost(request))
        kind = request.get("type")
        if kind == "consume":
            self.singles += 1
            if self.fail:
                self.fail = False
                raise RpcError("injected: peer down")
        resp = self._inner.call(addr, request, timeout=timeout)
        if kind == "consume.multi":
            listed = [x["partition"] for x in request["parts"]]
            self.fetches.append(listed)
            if self.refuse in listed and resp.get("ok"):
                resp["parts"][listed.index(self.refuse)] = {
                    "ok": False, "error": "not_leader"}
                self.refuse = None
        return resp

    def call_async(self, addr, request):
        return self._inner.call_async(addr, request)

    def close(self) -> None:
        self._inner.close()


def _cost(multi: float, single: float = 0.0):
    return lambda request: {"consume.multi": multi,
                            "consume": single}.get(request.get("type"), 0.0)


# name: partitions (all on one leader; led by the controller or not), the
# caller's schedule (`gap`: seconds of WALL time from poll to poll, a
# late caller polling at once, as benchmarks/child.py goes round; `own`:
# seconds the caller itself spends before every poll), what a request
# costs, and the width every fetch must have from rotation `settled` on
# (1: all but the learning rotation).
CADENCES = {
    # (a) late and back to back behind a 100 ms request: the whole
    # rotation in ONE request (on the wall clock: eight of one part)
    "back_to_back_slow_request": dict(
        n=8, ctl=True, gap=0.0, own=0.0001, cost=_cost(0.1), width=(8, 8)),
    # (b) on steady's schedule, 7.8 ms apart with a 2.5 ms request:
    # what the caller asks for within 60 ms - the one asked for and the
    # eight behind it (5.3 + 7 x 7.8 ms on the caller's clock), once the
    # learning rotation's stamps (a request each: 5.3 ms apart) are gone
    "scheduled_fast_request": dict(
        n=12, ctl=True, gap=0.0078, own=0.0, cost=_cost(0.0025),
        width=(9, 9), settled=2),
    # (c) 70 ms apart, a fast request: sessions of one, as ever
    "apart_fast_request": dict(
        n=3, ctl=False, gap=0.07, own=0.0, cost=_cost(0.001), width=(1, 1)),
    # (d) 70 ms apart of which 65 are the caller's OWN sleep and 5 the
    # request: still one part - the caller's own time counts
    "apart_by_its_own_sleep": dict(
        n=3, ctl=False, gap=0.0, own=0.065, cost=_cost(0.005),
        width=(1, 1)),
    # (e) the single path's failed attempt and back-off (>= 100 ms on
    # the policy's clock) are time the client held the caller
    "fallback_retries_and_backoff": dict(
        n=4, ctl=True, gap=0.0, own=0.0001, cost=_cost(0.001, 0.005),
        width=(3, 4), trouble=2),
}


@pytest.mark.parametrize("name", list(CADENCES))
def test_session_width_follows_the_callers_clock(cluster, name):
    case = CADENCES[name]
    parts = take(cluster, case["n"], controller=case["ctl"])
    sent = fill(cluster, parts, name.encode()[:6])
    strict = make_consumer(cluster, f"strict-{name}", max_messages=2)
    clock = Clock()
    slow = Slow(cluster.client(f"slow-{name}"), clock, case["cost"])
    ra = make_consumer(
        cluster, f"ra-{name}", transport=slow, prefetch=1, max_messages=2,
        retry_policy=RetryPolicy(max_attempts=3, base_backoff_s=0.2,
                                 clock=clock, sleep=slow.hold))
    ra._clock = clock
    try:
        want, _ = rotate(strict, parts)
        got = {p: [] for p in parts}
        stamp: dict[int, float] = {}  # the caller's clock at its polls
        per_rotation = []
        nxt = clock.t
        for r in range(6):
            mark = len(slow.fetches)
            if r == case.get("trouble"):
                slow.refuse, slow.fail = parts[1], True
            for p in parts:
                clock.tick(case["own"])
                nxt = max(nxt + case["gap"], clock.t)
                clock.t = nxt  # sleeps to its schedule; late: at once
                before, stamp[p] = stamp.get(p), clock.t - slow.held
                at = len(slow.fetches)
                msgs = ra.consume(WIDE, partition=p)
                if msgs:
                    got[p].append(msgs)
                for listed in slow.fetches[at:]:
                    # whoever rides along was asked for within 60 ms
                    # after `p` last was, on the caller's clock
                    assert listed[0] == p
                    for q in listed[1:]:
                        assert -1e-9 <= stamp[q] - before \
                            <= consumer_mod._ANSWER_MAX_AGE_S + 1e-9
            per_rotation.append(slow.fetches[mark:])
        lo, hi = case["width"]
        for fetches in per_rotation[case.get("settled", 1):]:
            assert fetches and all(lo <= len(listed) <= hi for listed in fetches), \
                per_rotation
        if name == "back_to_back_slow_request":
            # from the second rotation on ONE request a rotation
            assert [len(f) for f in per_rotation] == [8, 1, 1, 1, 1, 1]
        if name == "fallback_retries_and_backoff":
            r = case["trouble"]
            # the refused part went the single path: one failed attempt,
            # a back-off of 100-200 ms on the wall, one that served ...
            assert slow.singles == 2 and clock.t - 1000.0 > 0.1
            # ... and the rotation after it is still ONE request of all
            # four: the polls behind the trouble were not "150 ms later"
            assert per_rotation[r + 1] == [parts]
        # what the strict client reads: same messages, same order, same
        # windows, same committed offsets
        for p in parts:
            assert flat(got[p]) == flat(want[p]) == sent[p]
            assert [len(w) for w in got[p]] == [2, 2, 1]
        ra.flush_commits()
        for p in parts:
            assert committed(cluster, f"ra-{name}", p) \
                == committed(cluster, f"strict-{name}", p)
    finally:
        strict.close()
        ra.close()


# -- (b) an answer is handed out once; leaving the session ----------------


def test_uncollected_answer_moves_nothing_and_idle_partition_leaves(cluster):
    parts = take(cluster, 3)
    p0, p1, p2 = parts
    ra = make_consumer(cluster, "ra-b", prefetch=1)
    try:
        rotate(ra, parts, rotations=2)  # learnt, all at a known position
        sent = fill(cluster, [p2], b"b")
        ra._clock.tick()
        assert ra.consume(WIDE, partition=p0) == []  # fetched p1, p2 too
        assert ra._sess[(WIDE, p2)].answer[0] == sent[p2]
        before = counters(cluster)
        got, made = rotate(ra, [p0, p1], rotations=5)
        after = counters(cluster)
        # p2's answer was never collected: not fetched again (nor p1's,
        # in hand from that same fetch, in the first of these rotations)
        assert delta(after, before, "consume.multi_parts") == 2 * made - 1
        # ... and nothing of it committed, its position where it was
        ra.flush_commits()
        assert committed(cluster, "ra-b", p2) == 0
        assert ra._sess[(WIDE, p2)].pos < ra._sess[(WIDE, p2)].answer[2]
        # no longer polled: it leaves the session, answer and all
        ra._clock.tick(consumer_mod._SESSION_IDLE_S + 1)
        rotate(ra, [p0, p1], rotations=1)
        assert (WIDE, p2) not in ra._sess
        # polled again, it starts from the committed offset: the
        # messages once fetched and never handed out come now, once
        ra._clock.tick()
        assert ra.consume(WIDE, partition=p2) == sent[p2]
        ra._clock.tick()
        assert ra.consume(WIDE, partition=p2) == []
    finally:
        ra.close()


# -- (c) commits ----------------------------------------------------------


class Held:
    """Transport proxy: async `offset.commit.multi` requests are HELD
    until the test lets them reach the broker (a broker's worker pool
    may run two requests of one connection in either order), or answered
    as the test says; the synchronous requests it passes are counted."""

    def __init__(self, inner, answer=None) -> None:
        self._inner = inner
        self._answer = answer
        self.held: list = []   # (addr, request, future)
        self.calls: list = []  # types of the synchronous requests

    def call(self, addr, request, timeout=3.0):
        self.calls.append(request.get("type"))
        return self._inner.call(addr, request, timeout=timeout)

    def call_async(self, addr, request):
        if request.get("type") != "offset.commit.multi":
            return self._inner.call_async(addr, request)
        fut: Future = Future()
        self.held.append((addr, request, fut))
        if self._answer is not None:
            fut.set_result(self._answer(request))
        return fut

    def land(self, i: int) -> None:
        addr, request, fut = self.held[i]
        fut.set_result(self._inner.call(addr, request))

    def close(self) -> None:
        self._inner.close()


def test_one_commit_in_flight_per_leader_newest_parked(cluster):
    parts = take(cluster, 3)
    fill(cluster, parts, b"c1", n=6)
    transport = Held(cluster.client("held-c1"))
    ra = make_consumer(cluster, "ra-c1", transport=transport, prefetch=1,
                       max_messages=2)
    try:
        got, _ = rotate(ra, parts)
        assert all(len(flat(got[p])) == 6 for p in parts)
        # Nine windows handed out, not one commit landed: exactly ONE
        # request went out, everything newer is parked behind it.
        assert len(transport.held) == 1
        first = {x["partition"]: x["offset"]
                 for x in transport.held[0][1]["parts"]}
        parked = dict(ra._commits[transport.held[0][0]].owed)
        assert set(p for _, p in parked) == set(parts)
        assert all(parked[(WIDE, p)] > first.get(p, 0) for p in parts)
        seen = []
        transport.land(0)
        seen.append([committed(cluster, "ra-c1", p) for p in parts])
        ra.flush_commits()  # the parked offsets go out, synchronously
        assert len(transport.held) == 1
        assert transport.calls.count("offset.commit.multi") == 1
        seen.append([committed(cluster, "ra-c1", p) for p in parts])
        # the broker's offsets only ever grew, and end where delivery did
        assert all(a <= b for a, b in zip(*seen))
        assert seen[-1] == [ra._sess[(WIDE, p)].pos for p in parts]
    finally:
        ra.close()


def test_failed_commit_is_redriven_before_anything_newer(cluster):
    parts = take(cluster, 2)
    fill(cluster, parts, b"c2", n=4)

    def refuse(request):  # the whole first request fails, then parts do
        if len(transport.held) == 1:
            return {"ok": False, "error": "not_committed: lost"}
        return {"ok": True, "parts": [
            {"ok": False, "error": "not_committed: lost"}
            for _ in request["parts"]]}

    transport = Held(cluster.client("held-c2"), answer=refuse)
    ra = make_consumer(cluster, "ra-c2", transport=transport, prefetch=1,
                       max_messages=2)
    try:
        rotate(ra, parts)
        # every async request "failed": each offset was committed again
        # through the single-partition request, newest per partition
        assert transport.calls.count("offset.commit") >= len(parts)
        ra.flush_commits()
        assert [committed(cluster, "ra-c2", p) for p in parts] \
            == [ra._sess[(WIDE, p)].pos for p in parts]
    finally:
        ra.close()


def test_close_lands_every_commit(cluster):
    parts = take(cluster, 4)
    sent = fill(cluster, parts, b"c3")
    ra = make_consumer(cluster, "ra-c3", prefetch=1)
    got, _ = rotate(ra, parts, rotations=2)
    assert {p: flat(got[p]) for p in parts} == sent
    pos = [ra._sess[(WIDE, p)].pos for p in parts]
    ra.close()
    assert [committed(cluster, "ra-c3", p) for p in parts] == pos
    again = make_consumer(cluster, "ra-c3")  # strict, the same name
    try:
        assert rotate(again, parts)[0] == {p: [] for p in parts}
    finally:
        again.close()


def test_client_killed_before_its_commit_redelivers_and_skips_nothing(
        cluster):
    parts = take(cluster, 2)
    sent = fill(cluster, parts, b"c4")
    transport = Held(cluster.client("held-c4"))
    ra = make_consumer(cluster, "ra-c4", transport=transport, prefetch=1)
    got, _ = rotate(ra, parts, rotations=2)
    assert {p: flat(got[p]) for p in parts} == sent
    # killed here: handed out, no commit ever reached the broker
    assert all(committed(cluster, "ra-c4", p) == 0 for p in parts)
    ra._meta.close()
    heir = make_consumer(cluster, "ra-c4", prefetch=1)
    try:
        got, _ = rotate(heir, parts)
        assert {p: flat(got[p]) for p in parts} == sent
    finally:
        heir.close()


# -- (d) a part refused, its siblings served ------------------------------


def test_not_leader_part_falls_to_the_single_path(cluster):
    here = take(cluster, 2)
    (there,) = take(cluster, 1, controller=False)
    sent = fill(cluster, here + [there], b"d1")
    ra = make_consumer(cluster, "ra-d1", prefetch=1)
    try:
        parts = here + [there]
        rotate(ra, parts, rotations=1)  # learn; delivers everything
        more = fill(cluster, parts, b"d1x")
        # stale metadata: the client takes `there` for the controller's
        ra._sess[(WIDE, there)].addr = ra._sess[(WIDE, here[0])].addr
        before = counters(cluster)
        got, _ = rotate(ra, parts, rotations=2)
        after = counters(cluster)
        assert {p: flat(got[p]) for p in parts} == more
        # refused as a part, it went the single path and came back to
        # its own leader's session
        assert delta(after, before, "consume.multi_parts") >= 3
        assert ra._sess[(WIDE, there)].addr \
            == cluster.broker_addr(cluster.leader_of_key(WIDE, there))
        assert sent  # (first fill delivered in the learning rotation)
    finally:
        ra.close()


def test_stale_generation_part_is_refused_alone(cluster):
    p0, p1 = take(cluster, 2)
    sent = fill(cluster, [p0, p1], b"d2")
    rpc = cluster.client("raw-d2")
    addr = cluster.broker_addr(cluster.controller_id())
    resp = rpc.call(addr, {
        "type": "consume.multi", "consumer": "raw-d2", "parts": [
            {"topic": WIDE, "partition": p0, "offset": 0},
            {"topic": WIDE, "partition": p1, "offset": 0, "pgen": 7},
            {"topic": WIDE, "partition": 4096, "offset": 0},
            {"topic": WIDE, "partition": p1, "offset": -1},
            "not a part",
        ]})
    assert resp["ok"]
    a0, a1, a2, a3, a4 = resp["parts"]
    assert a0["ok"] and list(a0["messages"]) == sent[p0]
    assert a1["error"].startswith("stale_partition_gen:") and a1["routing"]
    assert a2["error"].startswith("unknown_partition")
    assert a3["error"] == "bad_request: negative offset"
    assert a4["error"].startswith("bad_request")
    assert rpc.call(addr, {"type": "consume.multi", "consumer": "raw-d2",
                           "parts": []})["error"].startswith("bad_request")


def test_group_fenced_commit_part_is_refused_alone(cluster):
    rpc = cluster.client("raw-d3")
    addr = cluster.broker_addr(cluster.controller_id())
    view = rpc.call(addr, {"type": "group.join", "group": "g-d3",
                           "member": "m1", "topics": [WIDE]})
    assert view["ok"], view
    own = [p for _, p in view["assignment"]
           if cluster.leader_of_key(WIDE, p) == cluster.controller_id()]
    p0, p1 = own[:2]
    req = {"type": "offset.commit.multi", "consumer": "g/g-d3",
           "group": "g-d3", "member": "m1",
           "generation": view["generation"],
           "parts": [
               {"topic": WIDE, "partition": p0, "offset": 3},
               # a deposed member's view of the group
               {"topic": WIDE, "partition": p1, "offset": 3,
                "generation": view["generation"] - 1},
           ]}
    resp = rpc.call(addr, req)
    assert resp["ok"]
    assert resp["parts"][0] == {"ok": True}
    assert resp["parts"][1]["error"].startswith("fenced_generation:")
    assert committed(cluster, "g/g-d3", p0) == 3
    assert committed(cluster, "g/g-d3", p1) == 0


# -- (e) a leader that is not the controller ------------------------------


def test_leader_that_is_not_the_controller_forwards_one_call(cluster):
    parts = take(cluster, 3, controller=False)
    sent = fill(cluster, parts, b"e")
    leader = cluster.brokers[cluster.leader_of_key(WIDE, parts[0])]
    forwarded: list[str] = []
    inner = leader.client.call

    def spy(addr, request, timeout=3.0):
        if str(request.get("type", "")).startswith("engine."):
            forwarded.append(request["type"])
        return inner(addr, request, timeout=timeout)

    strict = make_consumer(cluster, "strict-e", max_messages=2)
    ra = make_consumer(cluster, "ra-e", prefetch=1, max_messages=2)
    leader.client.call = spy
    try:
        before = counters(cluster)
        got, made = rotate(ra, parts)
        ra.flush_commits()
        after = counters(cluster)
        reads = delta(after, before, "consume.multi_requests")
        assert reads == len(parts) + made - 1
        # one engine call a request, whatever its parts; never a part's
        assert forwarded.count("engine.read_multi") == reads
        assert forwarded.count("engine.offsets_multi") \
            == delta(after, before, "commit.multi_requests") >= 1
        assert set(forwarded) == {"engine.read_multi",
                                  "engine.offsets_multi"}
        del leader.client.call
        want, _ = rotate(strict, parts)
        assert {p: flat(got[p]) for p in parts} \
            == {p: flat(want[p]) for p in parts} == sent
        assert [committed(cluster, "ra-e", p) for p in parts] \
            == [committed(cluster, "strict-e", p) for p in parts]
    finally:
        leader.client.__dict__.pop("call", None)
        strict.close()
        ra.close()


# -- (f) the strict client, (g) the instruments ---------------------------


def test_strict_client_sends_no_multi_request(cluster):
    parts = take(cluster, 2)
    sent = fill(cluster, parts, b"f")
    strict = make_consumer(cluster, "strict-f")
    try:
        before = counters(cluster)
        got, _ = rotate(strict, parts)
        after = counters(cluster)
        assert {p: flat(got[p]) for p in parts} == sent
        for name in ("consume.multi_requests", "consume.multi_parts",
                     "commit.multi_requests", "commit.multi_parts"):
            assert delta(after, before, name) == 0
        assert not strict._sess and not strict._commits
    finally:
        strict.close()


def test_instruments_read_on_the_multi_path(cluster):
    parts = take(cluster, 4)
    ctl = cluster.brokers[cluster.controller_id()]
    ra = make_consumer(cluster, "ra-g", prefetch=1)
    try:
        rotate(ra, parts, rotations=2)
        sent = fill(cluster, parts, b"g")
        m0 = ctl.metrics.snapshot()
        got, _ = rotate(ra, parts, rotations=1)  # ONE request, 4 parts
        m1 = ctl.metrics.snapshot()
        assert {p: flat(got[p]) for p in parts} == sent

        def hist(m, name):
            return m["histograms"].get(name, {}).get("count", 0)

        # the whole RPC once, the read once around all parts ...
        assert hist(m1, "consume.ack_us") - hist(m0, "consume.ack_us") == 1
        assert hist(m1, "read.serve_us") - hist(m0, "read.serve_us") == 1
        # ... a call and its payload counted per partition read
        assert delta(m1["counters"], m0["counters"], "read.calls") == 4
        assert delta(m1["counters"], m0["counters"], "read.bytes") \
            == sum(len(m) for p in parts for m in sent[p])
        assert delta(m1["counters"], m0["counters"], "read.messages") == 20
    finally:
        ra.close()


# -- the plane's part: read_many and submit_offsets_many ------------------


@pytest.fixture(scope="module")
def plane():
    """A bare plane whose ring (64 rows) has wrapped: partition 0 holds
    200 messages, so its oldest are below trim and come from the store."""
    from ripplemq_tpu.broker.dataplane import DataPlane
    from ripplemq_tpu.storage.memstore import MemoryRoundStore
    from tests.helpers import small_cfg

    cfg = small_cfg(partitions=4, slots=64, max_batch=8, read_batch=8,
                    max_consumers=4)
    dp = DataPlane(cfg, mode="local", store=MemoryRoundStore())
    dp.start()
    try:
        for p in range(cfg.partitions):
            dp.set_leader(p, 0, 1)
        for i in range(0, 200, 4):
            dp.submit_append(0, [b"w-%03d" % j for j in range(i, i + 4)]
                             ).result(timeout=30)
        for i in range(6):
            dp.submit_append(1, [b"x-%d" % i]).result(timeout=30)
        dp.submit_offsets(1, [(2, 3)]).result(timeout=30)
        yield dp
    finally:
        dp.stop()


@pytest.mark.parametrize("limit", [None, 1, 3])
def test_read_many_answers_what_read_answers(plane, limit):
    end = plane.settled_end(0)
    assert int(plane.trim[0]) > 0  # the ring wrapped: store reads below
    offsets = [0, 5, int(plane.trim[0]) - 1, int(plane.trim[0]),
               end - 9, end - 1, end, end + 100]
    items = [(0, off, 0, 0, limit) for off in offsets] \
        + [(1, 2, 0, 0, limit), (2, 0, 0, 0, limit)]
    calls0 = plane._m_read_calls.n
    got = plane.read_many(items)
    assert plane._m_read_calls.n - calls0 == len(items)
    for (slot, off, _, replica, _), ans in zip(items, got):
        msgs, nxt = plane.read(slot, off, replica, limit)
        assert ans == (msgs, off, nxt), (slot, off)


def test_read_many_resolves_the_committed_offset_and_refuses_per_item(plane):
    got = plane.read_many([
        (1, None, 2, 0, None),   # consumer slot 2 committed 3 above
        (1, None, 1, 0, None),   # nothing committed: from 0
        (9, 0, 0, 0, None),      # no such partition
        (1, None, 99, 0, None),  # no such consumer slot
    ])
    assert got[0] == (plane.read(1, 3, 0)[0], 3, plane.read(1, 3, 0)[1])
    assert got[1] == ([b"x-0"], 0, plane.read(1, 0, 0)[1])
    assert isinstance(got[2], ValueError) and isinstance(got[3], ValueError)


def test_submit_offsets_many_is_submit_offsets_under_one_hold(plane):
    futs = plane.submit_offsets_many([
        (0, [(1, 40)]), (1, [(1, 4)]), (9, [(1, 1)]), (2, [(7, 1)]),
        (3, []),
    ])
    assert futs[0].result(timeout=30) and futs[1].result(timeout=30)
    assert plane.read_offset(0, 1) == 40 and plane.read_offset(1, 1) == 4
    for bad in futs[2:]:
        with pytest.raises(ValueError):
            bad.result(timeout=1)


def test_read_many_locks_where_a_settled_gap_or_dirty_shadow_is(plane):
    """The unlocked look is for healthy partitions only: across a
    settled gap (a replication-FAILED round the horizon passed) and on a
    dirty shadow the answer is `read`'s, rows of the gap never served."""
    for i in range(4):
        plane.submit_append(3, [b"g-%d" % i]).result(timeout=30)
    end = plane.settled_end(3)
    first = plane.read(3, 0, 0)[1]  # the first round's padded extent
    with plane._lock:
        plane._add_settled_gap_locked(3, first, 2 * first)
    try:
        items = [(3, off, 0, 0, None) for off in (0, first, first + 1,
                                                   2 * first, end)]
        got = plane.read_many(items)
        for (slot, off, _, replica, _), ans in zip(items, got):
            msgs, nxt = plane.read(slot, off, replica)
            assert ans == (msgs, off, nxt), off
        assert b"g-1" not in [m for ans in got for m in ans[0]]
        with plane._lock:
            plane._shadow_dirty.add(2)
        assert plane.read_many([(2, 0, 0, 0, None)])[0] \
            == ([], 0, plane.read(2, 0, 0)[1])
    finally:
        with plane._lock:
            plane._settled_gaps.pop(3, None)
            plane._shadow_dirty.discard(2)
