"""The parked fetch (PR 43): a `consume.multi` (or `consume`) with
`wait_s` whose every part is empty parks ONCE, for all its parts, on a
waiter the settle thread's release feeds, and a long-polling readahead
client keeps its session - one such fetch a leader in flight, no poll
ever standing on a park.

How the in-process transport is driven: a request that carries `wait_s`
parks in its handler, so the raw requests of the broker cases run on a
thread of the test's (`Parked`), and the client's go through
`InProcClient.call_async`, which runs a request with `wait_s` on a
thread of its own (every other in-process call stays inline).

P = 16 throughout: three brokers, one topic of sixteen partitions RF 3 -
the controller holds a replica of every partition and leads them all -
and four brokers for the forwarded path, where a quarter are led by a
broker that is not the controller.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from ripplemq_tpu.chaos.cluster import small_engine
from ripplemq_tpu.client import ConsumerClient, ProducerClient
from ripplemq_tpu.core.config import ALIGN
from ripplemq_tpu.metadata.models import Topic
from tests.broker_harness import InProcCluster, make_config

T = "tail"
P = 16
READ_BATCH = 8


def make_cluster(n_brokers: int = 3):
    return InProcCluster(make_config(
        n_brokers=n_brokers, topics=(Topic(T, P, 3),),
        engine=small_engine(partitions=P, replicas=3, slots=256,
                            max_consumers=32, read_batch=READ_BATCH),
        metadata_election_timeout_s=0.6,
    ))


@pytest.fixture(scope="module")
def cluster():
    with make_cluster() as c:
        c.wait_for_leaders()
        yield c


@pytest.fixture()
def fresh():
    """A cluster of the test's own: for the cases that stop or depose."""
    with make_cluster() as c:
        c.wait_for_leaders()
        yield c


@pytest.fixture(scope="module")
def wide():
    with make_cluster(4) as c:
        c.wait_for_leaders()
        yield c


def bootstrap(c):
    return [b.address for b in c.config.brokers]


def controller(c):
    return c.brokers[c.controller_id()]


def produce(c, partition: int, msgs: list) -> tuple[int, float]:
    """(base offset, time.monotonic() at the ack)."""
    leader = c.leader_broker(T, partition)
    resp = c.client("raw-p").call(
        leader.addr, {"type": "produce", "topic": T, "partition": partition,
                      "messages": list(msgs)}, timeout=10.0)
    assert resp.get("ok"), resp
    return int(resp["base_offset"]), time.monotonic()


def ends(c) -> list[int]:
    dp = controller(c).dataplane
    return [dp.settled_end(p) for p in range(P)]


class Parked:
    """One raw request on a thread of its own: `resp` and when it came."""

    def __init__(self, c, addr: str, req: dict, name: str = "raw-c") -> None:
        self.resp = None
        self.t_done = None
        self.t_sent = time.monotonic()

        def run() -> None:
            try:
                self.resp = c.client(name).call(addr, req, timeout=15.0)
            except Exception as e:  # shown by the assertion on resp
                self.resp = {"raised": f"{type(e).__name__}: {e}"}
            self.t_done = time.monotonic()

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()

    def done(self, timeout: float) -> bool:
        self.thread.join(timeout)
        return not self.thread.is_alive()


def multi(consumer: str, positions: dict, wait_s=None, limit: int = 8) -> dict:
    req = {"type": "consume.multi", "consumer": consumer,
           "parts": [{"topic": T, "partition": p, "offset": off,
                      "max_messages": limit}
                     for p, off in positions.items()]}
    if wait_s is not None:
        req["wait_s"] = wait_s
    return req


def wait_parked(dp, n: int = 1, timeout: float = 5.0) -> None:
    deadline = time.monotonic() + timeout
    while dp._n_parks < n:
        assert time.monotonic() < deadline, f"{dp._n_parks} of {n} parked"
        time.sleep(0.002)


def fetch_counters(broker) -> dict:
    return {k: v for k, v in broker.metrics.snapshot()["counters"].items()
            if k.startswith("fetch.")}


# ------------------------------------------------------------- the broker

@pytest.mark.parametrize("hit", [0, 5, 15])
def test_multi_parked_on_sixteen_is_woken_by_any_one(cluster, hit):
    """(i) sixteen empty partitions, one request, one park: an append to
    ANY one of them settling answers it - that part's rows, the others
    empty - within a few ms of the ack, not at the deadline."""
    ctl = controller(cluster)
    before = fetch_counters(ctl)
    pos = dict(enumerate(ends(cluster)))
    req = Parked(cluster, ctl.addr, multi(f"g-any-{hit}", pos, wait_s=8.0))
    wait_parked(ctl.dataplane)
    assert not req.done(0.15), "answered with nothing settled"
    msgs = [b"hit-%d-%d" % (hit, i) for i in range(3)]
    base, t_ack = produce(cluster, hit, msgs)
    assert req.done(5.0), "the settle did not wake the park"
    assert req.t_done - t_ack < 0.25, req.t_done - t_ack
    parts = req.resp["parts"]
    assert [p["messages"] for p in parts] == [
        msgs if i == hit else [] for i in range(P)]
    assert parts[hit]["offset"] == base == pos[hit]
    assert parts[hit]["next_offset"] == base + ALIGN
    after = fetch_counters(ctl)
    assert after["fetch.parked"] - before["fetch.parked"] == 1
    assert after["fetch.woken"] - before["fetch.woken"] == 1
    assert after["fetch.expired"] == before["fetch.expired"]
    assert after["fetch.answered"] - before["fetch.answered"] == 1


def test_multi_expires_empty_at_the_deadline(cluster):
    """(ii) nothing settles: answered at the deadline, every part empty
    and at its position."""
    ctl = controller(cluster)
    before = fetch_counters(ctl)
    served = ctl.metrics.histogram("consume.ack_us")
    count0, total0 = served.count, served.total
    pos = dict(enumerate(ends(cluster)))
    req = Parked(cluster, ctl.addr, multi("g-exp", pos, wait_s=0.4))
    assert req.done(5.0)
    assert 0.4 <= req.t_done - req.t_sent < 1.5
    # consume.ack_us times the handler's work, not the stand
    assert served.count - count0 == 1
    assert served.total - total0 < 200_000, served.total - total0
    assert [(p["messages"], p["offset"], p["next_offset"])
            for p in req.resp["parts"]] == [([], pos[i], pos[i])
                                            for i in range(P)]
    after = fetch_counters(ctl)
    assert after["fetch.expired"] - before["fetch.expired"] == 1
    assert after["fetch.woken"] == before["fetch.woken"]
    assert after["fetch.answered"] == before["fetch.answered"]


def test_multi_with_rows_or_without_wait_answers_at_once(cluster):
    """A request one of whose parts has rows does not park, and one
    without `wait_s` never does: answered as before PR 43."""
    ctl = controller(cluster)
    pos = dict(enumerate(ends(cluster)))
    produce(cluster, 3, [b"ready"])
    before = fetch_counters(ctl)
    t0 = time.monotonic()
    resp = cluster.client("raw-c").call(
        ctl.addr, multi("g-now", pos, wait_s=5.0), timeout=10.0)
    assert [p["messages"] for p in resp["parts"]] == [
        [b"ready"] if i == 3 else [] for i in range(P)]
    resp = cluster.client("raw-c").call(
        ctl.addr, multi("g-now", dict(enumerate(ends(cluster)))),
        timeout=10.0)
    assert all(p["ok"] and not p["messages"] for p in resp["parts"])
    assert time.monotonic() - t0 < 1.0
    assert fetch_counters(ctl)["fetch.parked"] == before["fetch.parked"]


def test_nothing_unsettled_is_ever_answered(cluster):
    """(iii) rows the device committed but the settle has not released
    (standby acks, persist) end no park: hold the settle, append, the
    park stands; let it go, the park is answered with the rows."""
    ctl = controller(cluster)
    dp = ctl.dataplane
    pos = dict(enumerate(ends(cluster)))
    gate = threading.Event()
    real = dp._persist_round
    dp._persist_round = lambda records: (gate.wait(10.0), real(records))[1]
    try:
        req = Parked(cluster, ctl.addr, multi("g-hold", pos, wait_s=8.0))
        wait_parked(dp)
        acked = {}
        prod = threading.Thread(
            target=lambda: acked.update(at=produce(cluster, 7, [b"held"])),
            daemon=True)
        prod.start()
        # the round commits on the device and waits in the settle thread
        deadline = time.monotonic() + 5.0
        while dp.log_end(7) <= pos[7]:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        assert not req.done(0.3), f"answered before the settle: {req.resp}"
        assert dp.settled_end(7) == pos[7] and "at" not in acked
    finally:
        gate.set()
        dp._persist_round = real
    assert req.done(5.0)
    prod.join(5.0)
    assert [p["messages"] for p in req.resp["parts"]] == [
        [b"held"] if i == 7 else [] for i in range(P)]


@pytest.mark.parametrize("how", ["stop", "deposed"])
def test_stop_and_deposition_release_every_park(fresh, how):
    """(iv) a broker that stops, or a controller deposed, answers every
    parked request with a refusal - at once, not at its deadline."""
    ctl = controller(fresh)
    dp = ctl.dataplane
    pos = dict(enumerate(ends(fresh)))
    reqs = [Parked(fresh, ctl.addr,
                   multi(f"g-rel-{i}", pos, wait_s=9.0), name=f"raw-{i}")
            for i in range(3)]
    reqs.append(Parked(fresh, ctl.addr, {
        "type": "consume", "topic": T, "partition": 2, "offset": pos[2],
        "consumer": "g-rel-one", "wait_s": 9.0}, name="raw-one"))
    wait_parked(dp, len(reqs))
    t0 = time.monotonic()
    if how == "stop":
        ctl.stop()
    else:
        other = next(b for b in fresh.brokers if b != ctl.broker_id)
        ctl.manager.current_controller = lambda: other
        ctl._fence_duty()
        assert ctl.dataplane is None
    for r in reqs:
        assert r.done(5.0), "a park outlived the plane"
        assert r.t_done - t0 < 3.0
    assert dp._n_parks == 0 and not dp._parks
    for r in reqs[:3]:
        assert r.resp["ok"] and len(r.resp["parts"]) == P
        for part in r.resp["parts"]:
            assert not part["ok"] and part["error"].startswith(
                "not_committed"), part
    assert not reqs[3].resp.get("ok"), reqs[3].resp
    assert "not_committed" in reqs[3].resp["error"]


class CountingLock:
    """`DataPlane._lock` with its takes counted by thread."""

    def __init__(self, lock) -> None:
        self._lock = lock
        self.takes: dict[int, int] = {}

    def acquire(self, *a, **kw):
        got = self._lock.acquire(*a, **kw)
        if got:
            me = threading.get_ident()
            self.takes[me] = self.takes.get(me, 0) + 1
        return got

    def release(self) -> None:
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


@pytest.mark.parametrize("end", ["woken", "expired"])
def test_a_park_takes_the_plane_lock_at_most_once(cluster, end):
    """(vii) between registration and wake a parked request takes
    `DataPlane._lock` no more than once (it takes it not at all), reads
    nothing and runs no tick - while nothing settles, nothing runs."""
    ctl = controller(cluster)
    dp = ctl.dataplane
    pos = dict(enumerate(ends(cluster)))
    lock = CountingLock(dp._lock)
    seen = {}
    real_park, real_read_many = dp.park, dp.read_many
    reads = []

    def park(pairs, timeout):
        me = threading.get_ident()
        seen["before"] = lock.takes.get(me, 0)
        seen["reads_before"] = len(reads)
        try:
            return real_park(pairs, timeout)
        finally:
            seen["after"] = lock.takes.get(me, 0)
            seen["reads_after"] = len(reads)

    dp._lock = lock
    dp.park = park
    dp.read_many = lambda items: (reads.append(1), real_read_many(items))[1]
    try:
        req = Parked(cluster, ctl.addr, multi(
            f"g-lock-{end}", pos, wait_s=8.0 if end == "woken" else 0.5))
        wait_parked(dp)
        time.sleep(0.3)  # parked, nothing settles
        if end == "woken":
            produce(cluster, 11, [b"wake"])
        assert req.done(5.0)
    finally:
        dp._lock, dp.park, dp.read_many = lock._lock, real_park, \
            real_read_many
    assert seen["after"] - seen["before"] <= 1, seen
    assert seen["after"] - seen["before"] == 0, seen
    assert seen["reads_after"] == seen["reads_before"], seen
    # one read before the park, one after a wake, none after an expiry
    assert len(reads) == (2 if end == "woken" else 1), reads


def test_release_wakes_the_parks_it_passed_and_no_others(cluster):
    """Two parks on disjoint partitions: a settle on one side ends that
    park alone (a wake is an interpreter hand-over); the histograms at
    each release say how many stood and how many it ended."""
    ctl = controller(cluster)
    dp = ctl.dataplane
    e = ends(cluster)
    low = Parked(cluster, ctl.addr, multi(
        "g-low", {p: e[p] for p in range(0, 8)}, wait_s=8.0), name="raw-l")
    high = Parked(cluster, ctl.addr, multi(
        "g-high", {p: e[p] for p in range(8, 16)}, wait_s=1.2), name="raw-h")
    wait_parked(dp, 2)
    hist = lambda: ctl.metrics.snapshot()["histograms"]  # noqa: E731
    woken0 = hist()["fetch.woken_per_release"]
    now0 = hist()["fetch.parked_now"]
    produce(cluster, 2, [b"low"])
    assert low.done(5.0)
    assert not high.done(0.2), "a park on other partitions was woken"
    assert high.done(5.0)
    assert all(not p["messages"] for p in high.resp["parts"])
    woken1, now1 = hist()["fetch.woken_per_release"], hist()["fetch.parked_now"]
    assert woken1["count"] - woken0["count"] == 1  # one release
    assert woken1["max"] >= 1 and now1["max"] >= 2
    assert now1["count"] - now0["count"] == 1
    assert hist()["fetch.park_us"]["count"] >= 2
    assert hist()["fetch.wake_late_us"]["count"] >= 1


def test_single_consume_parks_on_the_same_waiter(cluster):
    """The single-partition `consume` with `wait_s` (strict clients,
    third parties, `follower_reads` clients) stands on the same waiter:
    woken by its partition's settle, deaf to another's, empty at the
    deadline."""
    ctl = controller(cluster)
    dp = ctl.dataplane
    e = ends(cluster)
    req = Parked(cluster, ctl.addr, {
        "type": "consume", "topic": T, "partition": 9, "offset": e[9],
        "consumer": "g-one", "wait_s": 8.0})
    wait_parked(dp)
    produce(cluster, 10, [b"other"])
    assert not req.done(0.2)
    _, t_ack = produce(cluster, 9, [b"mine"])
    assert req.done(5.0) and req.t_done - t_ack < 0.25
    assert req.resp["messages"] == [b"mine"] and req.resp["offset"] == e[9]
    t0 = time.monotonic()
    resp = cluster.client("raw-c").call(ctl.addr, {
        "type": "consume", "topic": T, "partition": 9,
        "offset": req.resp["next_offset"], "consumer": "g-one",
        "wait_s": 0.3}, timeout=10.0)
    assert resp["ok"] and resp["messages"] == []
    assert time.monotonic() - t0 >= 0.3


def test_a_refused_part_answers_the_request_at_once(cluster):
    """A request with a part the broker refuses does not park: the
    client has to take that part elsewhere now; its siblings are served."""
    ctl = controller(cluster)
    pos = dict(enumerate(ends(cluster)))
    req = multi("g-ref", pos, wait_s=5.0)
    req["parts"].append({"topic": T, "partition": P + 3, "offset": 0})
    t0 = time.monotonic()
    resp = cluster.client("raw-c").call(ctl.addr, req, timeout=10.0)
    assert time.monotonic() - t0 < 1.0
    assert [p["ok"] for p in resp["parts"]] == [True] * P + [False]


def test_forwarded_multi_parks_on_the_controller(wide):
    """A leader that is not the controller forwards the request as ONE
    engine.read_multi frame that carries the wait: the park stands on
    the controller's plane, the leader counts how it went."""
    ctl = controller(wide)
    by_leader: dict[int, list[int]] = {}
    for p in range(P):
        by_leader.setdefault(wide.leader_of_key(T, p), []).append(p)
    lid, mine = next((b, ps) for b, ps in sorted(by_leader.items())
                     if b != ctl.broker_id)
    leader = wide.brokers[lid]
    dp = ctl.dataplane
    pos = {p: dp.settled_end(p) for p in mine}
    before = fetch_counters(leader), fetch_counters(ctl)
    calls0 = len(wide.net.calls)
    req = Parked(wide, leader.addr, multi("g-fwd", pos, wait_s=8.0))
    wait_parked(dp)
    msgs = [b"fwd-0", b"fwd-1"]
    _, t_ack = produce(wide, mine[-1], msgs)
    assert req.done(5.0) and req.t_done - t_ack < 0.3
    assert [p["messages"] for p in req.resp["parts"]] == [
        msgs if p == mine[-1] else [] for p in mine]
    frames = [c for c in wide.net.calls[calls0:]
              if c[2] == "engine.read_multi"]
    assert len(frames) == 1, frames
    after = fetch_counters(leader), fetch_counters(ctl)
    assert after[0]["fetch.parked"] - before[0]["fetch.parked"] == 1
    assert after[0]["fetch.woken"] - before[0]["fetch.woken"] == 1
    assert after[1]["fetch.parked"] == before[1]["fetch.parked"]
    # and it expires there too, inside the engine call's timeout
    pos = {p: dp.settled_end(p) for p in mine}
    req = Parked(wide, leader.addr, multi("g-fwd", pos, wait_s=0.3))
    assert req.done(5.0)
    assert all(p["ok"] and not p["messages"] for p in req.resp["parts"])
    assert fetch_counters(leader)["fetch.expired"] \
        - before[0]["fetch.expired"] == 1


# ----------------------------------------- the plain model of the park

class ParkModel:
    """The park's semantics, plainly: per partition a list of (offset,
    message) and a settled end; a fetch of {partition: offset} is
    answered when any listed offset is below its partition's end - each
    part with its messages at or past its offset - else at the deadline,
    empty."""

    def __init__(self, settled: list[int]) -> None:
        self.end = dict(enumerate(settled))
        self.log: dict[int, list] = {p: [] for p in self.end}

    def append(self, p: int, base: int, msgs: list) -> None:
        self.log[p] += [(base + i, m) for i, m in enumerate(msgs)]
        self.end[p] = base + -(-len(msgs) // ALIGN) * ALIGN

    def ready(self, positions: dict) -> bool:
        return any(off < self.end[p] for p, off in positions.items())

    def answer(self, positions: dict, limit: int) -> dict:
        # one read looks at a window of `read_batch` rows
        return {p: [m for o, m in self.log[p]
                    if off <= o < off + READ_BATCH][:limit]
                for p, off in positions.items()}


@pytest.mark.parametrize("seed", [431, 432, 433, 434])
def test_broker_against_the_plain_model(cluster, seed):
    """Seeded arrivals at P = 16: requests over random subsets of the
    partitions at a reader's positions, an append that settles during
    the park on a listed partition (woken: that part's rows), on one not
    listed (the park stands and expires; the rows are there for the next
    request that lists it, at once), or none (expires)."""
    rng = np.random.default_rng(seed)
    ctl = controller(cluster)
    dp = ctl.dataplane
    model = ParkModel(ends(cluster))
    position = dict(model.end)  # the reader's
    n = 0
    for step in range(8):
        listed = sorted(int(p) for p in rng.choice(
            P, size=int(rng.integers(1, P + 1)), replace=False))
        positions = {p: position[p] for p in listed}
        kind = str(rng.choice(["listed", "unlisted", "none"]))
        unlisted = [p for p in range(P) if p not in listed]
        if kind == "unlisted" and not unlisted:
            kind = "none"
        limit = int(rng.integers(1, 9))
        ready = model.ready(positions)
        req = Parked(cluster, ctl.addr, multi(
            f"g-model-{seed}", positions,
            wait_s=6.0 if kind == "listed" else 0.35, limit=limit))
        if not ready:
            wait_parked(dp)
        if kind != "none" and not ready:
            target = int(rng.choice(listed if kind == "listed" else unlisted))
            msgs = [b"s%d-%d-%d" % (seed, n + i, target)
                    for i in range(int(rng.integers(1, 6)))]
            n += len(msgs)
            base, _ = produce(cluster, target, msgs)
            model.append(target, base, msgs)
        assert req.done(8.0), (step, kind)
        took = req.t_done - req.t_sent
        want = model.answer(positions, limit)
        got = {p: part["messages"]
               for p, part in zip(listed, req.resp["parts"])}
        assert got == want, (step, kind, positions)
        if any(want.values()):
            assert took < 3.0, (step, kind, took)
        else:
            assert took >= 0.35, (step, kind, took)
        for p, part in zip(listed, req.resp["parts"]):
            assert part["offset"] == positions[p]
            assert positions[p] <= part["next_offset"] <= model.end[p]
            if len(want[p]) == len([1 for o, _ in model.log[p]
                                    if o >= positions[p]]):
                assert part["next_offset"] == max(positions[p],
                                                  model.end[p])
            position[p] = part["next_offset"]


# ------------------------------------------------------------- the client

class Tap:
    """A transport that notes every request it carries and how many
    long-polling fetches a leader has in flight at once."""

    def __init__(self, inner) -> None:
        self._inner = inner
        self.requests: list[tuple[str, dict]] = []
        self.in_flight: dict[str, int] = {}
        self.most_in_flight = 0
        self._lock = threading.Lock()

    def call(self, addr, request, timeout=3.0):
        self.requests.append((addr, request))
        return self._inner.call(addr, request, timeout=timeout)

    def call_async(self, addr, request):
        self.requests.append((addr, request))
        fut = self._inner.call_async(addr, request)
        if request.get("wait_s"):
            with self._lock:
                self.in_flight[addr] = self.in_flight.get(addr, 0) + 1
                self.most_in_flight = max(self.most_in_flight,
                                          self.in_flight[addr])

            def landed(_):
                with self._lock:
                    self.in_flight[addr] -= 1

            fut.add_done_callback(landed)
        return fut

    def of(self, kind: str) -> list[dict]:
        return [r for _, r in self.requests if r.get("type") == kind]


def tail_client(c, cid: str, **kw):
    tap = Tap(c.client(f"c-{cid}"))
    return ConsumerClient(bootstrap(c), cid, transport=tap,
                          metadata_refresh_s=0.5, max_messages=64,
                          **kw), tap


def rotate_until(cons, want: dict, limit_s: float = 5.0):
    """One thread going round all sixteen partitions, as the benchmark's
    consumer does (a short sleep after an empty rotation): what each
    partition delivered, when its first message came, and the longest
    any single `consume` call took."""
    got: dict[int, list] = {}
    first: dict[int, float] = {}
    longest = 0.0
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        for p in range(P):
            t0 = time.monotonic()
            msgs = cons.consume(T, partition=p)
            t1 = time.monotonic()
            longest = max(longest, t1 - t0)
            if msgs:
                got.setdefault(p, []).extend(msgs)
                first.setdefault(p, t1)
        if all(got.get(p) == m for p, m in want.items()):
            break
        time.sleep(0.002)
    return got, first, longest


def drain(cons, quiet_s: float = 0.3) -> dict:
    """Go round until nothing has come for `quiet_s`: the client has
    learned the sixteen partitions, read what the module's other cases
    left in them, and its leader's fetch is parked on all of them."""
    got: dict[int, list] = {}
    last = time.monotonic()
    while time.monotonic() - last < quiet_s:
        for p in range(P):
            msgs = cons.consume(T, partition=p)
            if msgs:
                got.setdefault(p, []).extend(msgs)
                last = time.monotonic()
        time.sleep(0.002)
    return got


def test_client_delivers_partition_5_while_partition_0_is_parked(cluster):
    """(v) the head-of-line case: one thread, sixteen partitions,
    `long_poll_s` 0.5. Every partition is empty and the leader's fetch
    is parked on all sixteen; a message on partition 5 is delivered
    within a few ms of its ack, and no poll - not partition 0's, not
    anyone's - ever stands on a park. (On the parent the client fell to
    one `consume` a partition and its poll of partition 0 stood on that
    partition's park for `long_poll_s`.)"""
    cons, tap = tail_client(cluster, "g-hol", prefetch=1, long_poll_s=0.5)
    try:
        drain(cons)  # learn the sixteen, park
        assert len(tap.of("consume.multi")) >= 1
        dp = controller(cluster).dataplane
        wait_parked(dp)
        msgs = [b"five-0", b"five-1"]
        _, t_ack = produce(cluster, 5, msgs)
        got, first, longest = rotate_until(cons, {5: msgs})
        assert got == {5: msgs}
        assert first[5] - t_ack < 0.25, first[5] - t_ack
        assert longest < 0.25, f"a poll stood {longest:.3f} s"
        # the session stayed on: no single-partition consume, every
        # long-polling fetch a consume.multi, one a leader in flight
        assert not tap.of("consume")
        parked = [r for r in tap.of("consume.multi") if r.get("wait_s")]
        assert parked and len(parked[-1]["parts"]) == P
        assert tap.most_in_flight == 1
    finally:
        cons.close()


def test_client_sends_one_request_a_delivery_and_commits_ride_multi(cluster):
    """A stream of deliveries: each costs about one consume.multi (the
    fetch is re-armed when its answer has been handed out and carries
    all sixteen again), commits ride offset.commit.multi, and a second
    consumer of the group starts where the first stopped."""
    cons, tap = tail_client(cluster, "g-stream", prefetch=1, long_poll_s=0.5)
    try:
        drain(cons)
        n0 = len([r for r in tap.of("consume.multi") if r.get("wait_s")])
        want: dict[int, list] = {}
        got: dict[int, list] = {}
        for i in range(12):
            p = (i * 5) % P
            m = [b"st-%d" % i]
            produce(cluster, p, m)
            want.setdefault(p, []).extend(m)
            g, _, longest = rotate_until(cons, {p: m}, limit_s=3.0)
            assert longest < 0.25
            for q, ms in g.items():
                got.setdefault(q, []).extend(ms)
        assert got == want
        sent = len([r for r in tap.of("consume.multi")
                    if r.get("wait_s")]) - n0
        assert 12 <= sent <= 12 * 2 + 2, sent
        assert tap.most_in_flight == 1
        assert tap.of("offset.commit.multi") and not tap.of("offset.commit")
    finally:
        cons.close()
    again, _ = tail_client(cluster, "g-stream", prefetch=1, long_poll_s=0.5)
    try:
        assert drain(again) == {}, "delivered and committed rows came again"
    finally:
        again.close()


@pytest.mark.parametrize("long_poll_s", [0.0, 0.5])
def test_wait_s_is_sent_only_by_a_long_polling_client(cluster, long_poll_s):
    """(vi) `long_poll_s` 0 sends no `wait_s` - the requests PR 40's
    session sends, key for key; 0.5 sends it on its consume.multi."""
    cons, tap = tail_client(cluster, f"g-wire-{long_poll_s}", prefetch=1,
                            long_poll_s=long_poll_s)
    try:
        produce(cluster, 1, [b"w"])
        drain(cons)
    finally:
        cons.close()
    fetches = tap.of("consume.multi")
    assert fetches
    with_wait = [r for r in fetches if "wait_s" in r]
    if long_poll_s == 0:
        assert not with_wait
        assert all(set(r) == {"type", "consumer", "parts"} for r in fetches)
    else:
        assert with_wait and all(r["wait_s"] <= 0.5 for r in with_wait)
    assert not any("wait_s" in r for _, r in tap.requests
                   if r.get("type") != "consume.multi")


def test_client_takes_a_refused_part_the_single_way(cluster):
    """A part the broker refuses leaves the leader's fetch for the
    single-partition `consume`, which re-resolves; its siblings are
    served by the fetch, and it returns to the session."""
    cons, tap = tail_client(cluster, "g-refused", prefetch=1,
                            long_poll_s=0.3)
    try:
        # the fetch that brings partition 4's rows has that part
        # refused instead, once
        inner = tap._inner
        real = inner.call_async
        state = {"left": 0}

        def call_async(addr, request):
            fut = real(addr, request)
            if not (request.get("type") == "consume.multi"
                    and request.get("wait_s")):
                return fut
            out = Future()

            def landed(f) -> None:
                resp = f.result()
                for i, part in enumerate(request["parts"]):
                    if (state["left"] and part["partition"] == 4
                            and resp["parts"][i].get("messages")):
                        state["left"] = 0
                        resp["parts"][i] = {"ok": False,
                                            "error": "not_leader"}
                out.set_result(resp)

            fut.add_done_callback(landed)
            return out

        inner.call_async = call_async
        drain(cons)
        state["left"] = 1
        produce(cluster, 4, [b"four"])
        produce(cluster, 6, [b"six"])
        got, _, _ = rotate_until(cons, {4: [b"four"], 6: [b"six"]})
        assert got == {4: [b"four"], 6: [b"six"]}
        assert state["left"] == 0
        singles = tap.of("consume")
        assert singles and {r["partition"] for r in singles} == {4}
        n_single = len(singles)
        produce(cluster, 4, [b"four-again"])
        got, _, _ = rotate_until(cons, {4: [b"four-again"]})
        assert got == {4: [b"four-again"]}
        assert len(tap.of("consume")) == n_single  # back in the session
    finally:
        cons.close()


# ------------------------------------------ the metric files of the cell

TAIL_METRICS = {"tail.wake_late_ms": 0.2, "tail.park_ms": 30.0,
                "tail.expired_share": 0.25, "tail.parked_share": 0.8,
                "tail.requests_per_delivery": 1.25,
                "tail.fetch_parts_per_request": 16.0}


@pytest.mark.parametrize("name", sorted(TAIL_METRICS))
def test_tail_metric_file_reads_its_surface(name):
    """Each new metric file of `omb-16p-1kb.tail`, read with its reader
    from a registry the `fetch.*` surfaces were observed into; on a
    program without them (the parent: the registry as it was before)
    the reader finds nothing and does not raise; file and
    BENCHMARK.json entry agree."""
    import importlib
    import json
    import os

    from benchmarks.readers._common import parse_exposition
    from ripplemq_tpu.obs.metrics import Metrics, render_prometheus

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = Metrics()
    # what the parent's registry holds of it: the session's counters
    m.counter("consume.multi_requests").inc(0)
    m.counter("consume.multi_parts").inc(0)
    parent = parse_exposition(render_prometheus(m))
    for series, values in {"fetch.wake_late_us": [100, 300],
                           "fetch.park_us": [20_000, 40_000]}.items():
        for v in values:
            m.histogram(series).observe_int(v)
    for series, n in {"fetch.parked": 8, "fetch.expired": 2,
                      "fetch.woken": 6, "fetch.answered": 8,
                      "consume.multi_requests": 10,
                      "consume.multi_parts": 160}.items():
        m.counter(series).inc(n)
    after = parse_exposition(render_prometheus(m))
    run = {"t0_ns": 100, "t1_ns": 200,
           "snapshots": [(101, parent), (199, after)]}
    old = {"t0_ns": 100, "t1_ns": 200,
           "snapshots": [(101, parent), (199, parent)]}
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(
        f"benchmarks.readers.{spec['reader']['kind']}")
    assert reader.read(spec["reader"]["args"], run) == pytest.approx(
        TAIL_METRICS[name])
    assert reader.read(spec["reader"]["args"], old) is None
    entry = next(e for e in bench["per_layer"] if e["name"] == name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert spec["workloads"] == entry["workloads"] == ["omb-16p-1kb.tail"]
    assert spec["moves"] == "deliver_p50_ms"
    e2e = next(e for e in bench["end_to_end"]
               if e["name"] == "deliver_p50_ms")
    assert "omb-16p-1kb.tail" in e2e["workloads"]
