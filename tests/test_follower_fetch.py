"""The fetch a follower serves (PR 47): a `consume.multi` marked
`follower_ok` that reaches a standby with a current-epoch lease is
answered whole from its `FollowerReadPlane` and parks on the plane's own
waiter, fed by the floor stamps of the standby stream; a `client_rack`
consumer keeps its session there.

Three layers. (1) A PLAIN model of what a follower may hand out - a few
dozen lines of pure Python: rows strictly below the floor, gaps skipped
as the leader skips them, nothing across an epoch - held against the
plane plus the standby's own handler over seeded random scripts of
rounds, gaps, floor stamps, epoch changes and fetches, with and without
`wait_s`. The handler is `BrokerServer`'s, bound to a stand-in that has
a plane, a registry and a lease table and nothing else: no cluster
boots for it. (2) The waiter: woken by the stamp that passes it and by
no other, expiring empty, refused on an epoch change, a lost lease and a
stop; and the sender's floor frames. (3) A three-broker in-proc cluster
in which a `client_rack` consumer receives byte for byte what a
leader-served consumer of the same log receives, across the follower's
death and a controller change.
"""

from __future__ import annotations

import random
import threading
import time
from types import SimpleNamespace

import pytest

from ripplemq_tpu.broker.follower import FollowerReadPlane, ParkRefused
from ripplemq_tpu.broker.replication import RoundReplicator
from ripplemq_tpu.broker.server import BrokerServer
from ripplemq_tpu.chaos.cluster import small_engine
from ripplemq_tpu.client import ConsumerClient, ProducerClient
from ripplemq_tpu.metadata.models import Topic
from ripplemq_tpu.obs.metrics import Metrics
from ripplemq_tpu.obs.spans import SpanRing
from ripplemq_tpu.storage.segment import REC_APPEND
from tests.broker_harness import InProcCluster, make_config
from tests.helpers import wait_until
from tests.test_follower_reads import SB, rows_of

T = "t"
SLOTS = 4


# ------------------------------------------------------ the plain model

class Model:
    """What a follower may hand out, from the script alone. Per slot: the
    log (`rows[i]`: a payload, b"" for a padding row, None where the
    leader nacked a round), the contiguous run of it the standby holds
    (`start`..`held`: a page that does not join on restarts it), the
    floor and the gaps the last stamp carried. An epoch change forgets
    everything."""

    def __init__(self) -> None:
        self.epoch = 1
        self.reset()

    def reset(self) -> None:
        self.rows = {s: [] for s in range(SLOTS)}
        self.start = {s: 0 for s in range(SLOTS)}
        self.held = {s: 0 for s in range(SLOTS)}
        self.floor: dict = {}
        self.gaps = {s: [] for s in range(SLOTS)}
        self.known_gaps = {s: [] for s in range(SLOTS)}

    def round(self, slot: int, payloads: list) -> int:
        base = len(self.rows[slot])
        self.rows[slot] += payloads
        if base != self.held[slot]:
            self.start[slot] = base  # past a gap: the run restarts
        self.held[slot] = base + len(payloads)
        return base

    def gap(self, slot: int, n: int) -> None:
        """A round the leader nacked: n offsets with no rows here, named
        by the stamps from now on."""
        base = len(self.rows[slot])
        self.rows[slot] += [None] * n
        self.gaps[slot].append([base, base + n])

    def stamp(self, slot: int, floor: int) -> None:
        self.floor[slot] = max(floor, self.floor.get(slot, -1))
        self.known_gaps[slot] = [list(g) for g in self.gaps[slot]]

    def read(self, slot: int, off: int, limit: int):
        """(messages, next_offset), or None: refused to the leader."""
        floor = self.floor.get(slot)
        if floor is None:
            return None
        if off >= floor:
            return [], off  # nothing settled here past it: empty, parkable
        for s, e in self.known_gaps[slot]:
            if s <= off < e:
                return [], min(e, floor)
        lim = min(self.held[slot], floor)
        if off < self.start[slot] or off >= lim:
            return None  # not held here: the leader has it
        msgs, pos = [], off
        while pos < lim and len(msgs) < limit:
            row = self.rows[slot][pos]
            assert row is not None, "the model read into a gap"
            pos += 1
            if row:
                msgs.append(row)
        return msgs, pos


    def fetch(self, pos: dict, limit: int, wait: bool) -> list:
        """A request's answers, part by part. One that waits and whose
        every part came back empty reads on from where each part ended
        for as long as a floor is past one of those positions (an
        empty-but-advanced answer keeps its advance), then parks."""
        want = [self.read(s, off, limit) for s, off in pos.items()]
        while wait and all(w is not None and not w[0] for w in want):
            if not any(self.floor[s] > w[1] for s, w in zip(pos, want)):
                break
            want = [self.read(s, w[1], limit) for s, w in zip(pos, want)]
        return want


# ------------------------------------------------ the standby's handler

class Standby:
    """`BrokerServer`'s consume.multi handler and what it calls, on a
    plane, a registry and a lease table: the broker's own code, no
    cluster."""

    for _name in ("_handle_consume_multi", "_follower_fetch",
                  "_follower_epoch", "_follower_park_duty", "_leader_hint",
                  "_gen_refusal", "_make_follower_plane", "_note_floor_lag",
                  "_LONG_POLL_CAP_S"):
        locals()[_name] = getattr(BrokerServer, _name)
    _part_refusal = staticmethod(BrokerServer._part_refusal)
    del _name

    def __init__(self, trace_sample_n: int = 0) -> None:
        self.broker_id = 1
        self.config = SimpleNamespace(
            replication="full", follower_reads=True,
            follower_page_cache_bytes=1 << 20, trace_sample_n=trace_sample_n,
            engine=SimpleNamespace(slot_bytes=SB))
        self.metrics = Metrics()
        self.spans = (SpanRing("broker-1", metrics=self.metrics)
                      if trace_sample_n else None)
        self._stop = threading.Event()
        self._gen_fence_refusals = 0
        self.epoch, self.lease = 1, 1
        view = SimpleNamespace(leader=0, generation=0, state="active")
        self.manager = SimpleNamespace(
            current_epoch=lambda: self.epoch,
            follower_lease=lambda b: self.lease,
            peek=lambda key: (view, key[1] if key[1] < SLOTS else None))
        self._make_follower_plane()

    def _addr_of(self, b: int) -> str:
        return f"broker:{9000 + b}"

    def _local_engine(self):
        return None

    def fetch(self, positions: dict, wait_s: float = 0.0, limit: int = 3):
        req = {"type": "consume.multi", "consumer": "c", "follower_ok": True,
               "parts": [{"topic": T, "partition": p, "offset": off,
                          "max_messages": limit}
                         for p, off in positions.items()]}
        if wait_s:
            req["wait_s"] = wait_s
        return self._handle_consume_multi(req)

    def counters(self) -> dict:
        return {k[len("follower."):]: v for k, v in
                self.metrics.snapshot()["counters"].items()
                if k.startswith("follower.")}


def answers(resp: dict) -> list:
    """(messages, next_offset) a part, None for a refused one."""
    assert resp["ok"] and resp["follower"], resp
    out = []
    for p in resp["parts"]:
        if p["ok"]:
            out.append((p["messages"], p["next_offset"]))
        else:
            assert p["error"].startswith("not_settled_here:"), p
            assert p["leader"] == 0 and p["leader_addr"] == "broker:9000"
            out.append(None)
    return out


class InFlight:
    """One request on a thread of the test's: it may park."""

    def __init__(self, fn) -> None:
        self.resp = None
        self.thread = threading.Thread(
            target=lambda: setattr(self, "resp", fn()), daemon=True)
        self.thread.start()

    def done(self, timeout: float) -> bool:
        self.thread.join(timeout)
        return not self.thread.is_alive()


def wait_parked(fp, n: int = 1) -> None:
    wait_until(lambda: fp.parked() >= n, timeout=5.0, interval=0.001)


@pytest.mark.parametrize("wait_s", [0.0, 0.03])
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_follower_hands_out_what_the_plain_model_allows(seed, wait_s):
    """A seeded script against model and program: every fetch answers,
    part by part, what the model allows - and with `wait_s` a request
    whose every part is at the floor parks, and is woken by the stamp
    that settles the round sent after it."""
    rng = random.Random(seed)
    sb, model = Standby(), Model()
    fp = sb.follower_plane
    cursor = {s: 0 for s in range(SLOTS)}
    n_msgs = fetches = parked = 0

    def payloads() -> list:
        nonlocal n_msgs
        out = []
        for _ in range(rng.randint(1, 5)):
            n_msgs += 1
            out.append(b"" if rng.random() < 0.2 else b"m%d" % n_msgs)
        return out

    def send_round(slot: int) -> None:
        ps = payloads()
        base = model.round(slot, ps)
        fp.ingest_rounds(model.epoch, [(REC_APPEND, slot, base,
                                        rows_of(ps))], None)

    def send_stamp(slot: int, floor: int) -> None:
        model.stamp(slot, floor)
        fp.ingest_rounds(model.epoch, [], [
            [slot, floor, [list(g) for g in model.gaps[slot]]]])

    def park_and_wake(pos: dict, limit: int, want: list) -> list:
        """The request parks; a round sent now on one of its slots and
        the stamp that settles it wake it with that round's rows."""
        nonlocal parked
        hit = rng.choice(list(pos))
        before = sb.counters()
        req = InFlight(lambda: sb.fetch(pos, 5.0, limit))
        wait_parked(fp)
        assert not req.done(0.01)
        at = dict(zip(pos, want))[hit][1]
        while len(model.rows[hit]) < at:
            send_round(hit)  # rows up to the parked position
        send_round(hit)
        send_stamp(hit, len(model.rows[hit]))
        assert req.done(5.0)
        after = sb.counters()
        assert after["fetch_parked"] - before["fetch_parked"] == 1
        assert after["fetch_woken"] - before["fetch_woken"] == 1
        parked += 1
        return answers(req.resp)

    def fetch(pos: dict, limit: int, wake: bool) -> None:
        nonlocal fetches
        want = model.fetch(pos, limit, bool(wait_s))
        fetches += 1
        if (wait_s and wake
                and all(w is not None and not w[0] for w in want)):
            got = park_and_wake(pos, limit, want)
            want = model.fetch(pos, limit, True)
        else:
            got = answers(sb.fetch(pos, wait_s, limit))
        assert got == want, (seed, pos, limit)
        for s, g in zip(pos, got):
            if g is not None:
                assert g[1] <= max(model.floor[s], pos[s])
                if cursor[s] == pos[s]:
                    cursor[s] = g[1]

    for _ in range(120):
        op = rng.random()
        slot = rng.randrange(SLOTS)
        if op < 0.30:
            send_round(slot)
        elif op < 0.36:
            model.gap(slot, rng.randint(1, 4))
        elif op < 0.62:
            lo = model.floor.get(slot, 0)
            send_stamp(slot, rng.randint(lo, len(model.rows[slot])))
        elif op < 0.66:
            model.epoch += 1
            model.reset()
            sb.epoch = sb.lease = model.epoch
            for s in range(SLOTS):  # a reader restarts at the new log
                cursor[s] = 0
        else:
            parts = rng.sample(range(SLOTS), rng.randint(1, SLOTS))
            fetch({s: (cursor[s] if rng.random() < 0.8
                       else rng.randint(0, len(model.rows[s]) + 2))
                   for s in parts},
                  rng.randint(1, 4), rng.random() < 0.5)
    # and once with every slot settled to its end and read from there
    for s in range(SLOTS):
        send_stamp(s, len(model.rows[s]))
    fetch({s: len(model.rows[s]) for s in range(SLOTS)}, 3, True)
    assert fetches > 20 and n_msgs > 50
    assert fp.stats()["answers_past_floor"] == 0
    if wait_s:
        assert parked > 0


# ----------------------------------------------------------- the waiter

def primed() -> Standby:
    """Slots 0..3, three rows each, all settled."""
    sb = Standby()
    for s in range(SLOTS):
        sb.follower_plane.ingest_rounds(
            1, [(REC_APPEND, s, 0, rows_of([b"a", b"b", b"c"]))],
            [[s, 3, []]])
    return sb


def test_a_park_is_woken_by_the_stamp_that_passes_it_and_no_other():
    sb = primed()
    fp = sb.follower_plane
    pos = {0: 3, 1: 3, 2: 5}
    req = InFlight(lambda: sb.fetch(pos, 5.0))
    wait_parked(fp)
    # rows without a stamp, a stamp that repeats the floor, a stamp for a
    # slot the request does not list, a stamp below the parked position
    fp.ingest_rounds(1, [(REC_APPEND, 0, 3, rows_of([b"d"]))], None)
    fp.ingest_rounds(1, [], [[0, 3, []]])
    fp.ingest_rounds(1, [(REC_APPEND, 3, 3, rows_of([b"x"]))], [[3, 4, []]])
    fp.ingest_rounds(1, [(REC_APPEND, 2, 3, rows_of([b"y", b"z"]))],
                     [[2, 5, []]])
    assert not req.done(0.1) and fp.parked() == 1
    fp.ingest_rounds(1, [], [[0, 4, []]])  # passes slot 0's position
    assert req.done(5.0)
    assert answers(req.resp) == [([b"d"], 4), ([], 3), ([], 5)]
    c = sb.counters()
    assert (c["fetch_parked"], c["fetch_woken"], c["fetch_expired"],
            c["fetch_answered"]) == (1, 1, 0, 1)
    hist = sb.metrics.snapshot()["histograms"]
    assert hist["follower.wake_late_us"]["count"] == 1
    assert hist["follower.park_us"]["count"] == 1
    # the request less its park
    assert hist["follower.serve_us"]["max"] < 100_000


def test_a_park_expires_empty_at_its_deadline():
    sb = primed()
    t0 = time.monotonic()
    resp = sb.fetch({0: 3, 1: 3}, 0.15)
    assert 0.15 <= time.monotonic() - t0 < 1.0
    assert answers(resp) == [([], 3), ([], 3)]
    c = sb.counters()
    assert (c["fetch_parked"], c["fetch_expired"], c["fetch_woken"],
            c["fetch_answered"]) == (1, 1, 0, 0)


def test_a_request_with_rows_or_a_refused_part_does_not_park():
    sb = primed()
    t0 = time.monotonic()
    # slot 1 has rows below the floor; partition 9 does not exist
    assert answers(sb.fetch({0: 3, 1: 1}, 5.0)) == [([], 3),
                                                    ([b"b", b"c"], 3)]
    resp = sb.fetch({0: 3, 9: 0}, 5.0)
    assert resp["parts"][0]["ok"] and not resp["parts"][1]["ok"]
    assert resp["parts"][1]["error"].startswith("unknown_partition")
    # a part without an offset is the leader's: its committed offset
    req = {"type": "consume.multi", "consumer": "c", "follower_ok": True,
           "wait_s": 5.0, "parts": [{"topic": T, "partition": 0}]}
    part, = sb._handle_consume_multi(req)["parts"]
    assert part["error"] == "not_leader" and part["leader"] == 0
    assert time.monotonic() - t0 < 1.0
    assert sb.counters()["fetch_parked"] == 0


@pytest.mark.parametrize("how", ["epoch", "lease", "stopped"])
def test_a_park_is_refused_when_its_ground_goes(how):
    """An epoch change reaches the plane with the new generation's first
    frame (or the broker's duty); a lost lease is the duty's to see; a
    stop releases every park. The whole request is refused."""
    sb = primed()
    fp = sb.follower_plane
    req = InFlight(lambda: sb.fetch({0: 3, 1: 3}, 5.0))
    wait_parked(fp)
    if how == "epoch":
        fp.ingest_rounds(2, [(REC_APPEND, 0, 3, rows_of([b"n"]))],
                         [[0, 4, []]])
    elif how == "lease":
        sb.lease = None
        sb._follower_park_duty()
    else:
        sb._stop.set()
        fp.release_parks("stopped")
    assert req.done(5.0)
    assert not req.resp["ok"]
    assert req.resp["error"].startswith("not_settled_here:")
    assert fp.parked() == 0
    c = sb.counters()
    assert c["fetch_woken"] == 0 and c["fetch_expired"] == 0
    # and from then on it serves nothing it should not
    if how == "epoch":
        sb.epoch = sb.lease = 2  # leased again under the new epoch:
        # the old generation's rows are gone, the new one's are served
        assert answers(sb.fetch({0: 0})) == [None]
        assert answers(sb.fetch({0: 3})) == [([b"n"], 4)]
    elif how == "lease":
        req = {"type": "consume.multi", "consumer": "c", "follower_ok": True,
               "parts": [{"topic": T, "partition": 0, "offset": 0}]}
        # no lease: the ordinary path's, which answers `not_leader`
        assert sb._follower_fetch(req, req["parts"]) is None


def test_a_woken_park_whose_lease_went_hands_out_nothing():
    """The lease is asked again between the wake and the rows."""
    sb = primed()
    fp = sb.follower_plane
    req = InFlight(lambda: sb.fetch({0: 3}, 5.0))
    wait_parked(fp)
    sb.lease = None
    fp.ingest_rounds(1, [(REC_APPEND, 0, 3, rows_of([b"d"]))], [[0, 4, []]])
    assert req.done(5.0)
    assert not req.resp["ok"] and "lease" in req.resp["error"]


def test_striped_and_unleased_standbys_leave_the_request_to_the_leader():
    sb = primed()
    sb.config.replication = "striped"
    req = {"type": "consume.multi", "consumer": "c", "follower_ok": True,
           "parts": [{"topic": T, "partition": 0, "offset": 0}]}
    assert sb._follower_fetch(req, req["parts"]) is None
    sb.config.replication = "full"
    sb.lease = 0  # another epoch's
    assert sb._follower_fetch(req, req["parts"]) is None
    assert sb.counters()["fetch_requests"] == 0


def test_an_untraced_consumers_fetch_is_sampled_by_the_broker():
    """No context on the request: every trace_sample_n-th roots its own
    follower.fetch, with its park and wake under it; an untraced broker
    records nothing."""
    sb = Standby(trace_sample_n=1)
    fp = sb.follower_plane
    fp.ingest_rounds(1, [(REC_APPEND, 0, 0, rows_of([b"a"]))], [[0, 1, []]])
    req = InFlight(lambda: sb.fetch({0: 1}, 5.0))
    wait_parked(fp)
    fp.ingest_rounds(1, [(REC_APPEND, 0, 1, rows_of([b"b"]))], [[0, 2, []]])
    assert req.done(5.0)
    sb._note_floor_lag(time.monotonic_ns() - 2_000_000)
    spans = {r["kind"]: r for r in sb.spans.snapshot()}
    assert set(spans) == {"follower.fetch", "follower.park",
                          "follower.wake", "follower.floor"}
    root = spans["follower.fetch"]
    assert (root["parent"], root["served"], root["refused"],
            root["rows"]) == (0, 1, 0, 1)
    assert spans["follower.park"]["parent"] == root["span"]
    assert spans["follower.wake"]["parent"] == root["span"]
    assert 1_900 < spans["follower.floor"]["dur_us"] < 500_000
    h = sb.metrics.snapshot()["histograms"]["follower.floor_lag_us"]
    assert h["count"] == 1
    assert Standby().spans is None


def test_plane_park_alone():
    """`FollowerReadPlane.park` without a handler: a floor already past
    ends it at once, another epoch refuses it, a timeout returns None."""
    fp = FollowerReadPlane(SB, 1 << 20)
    fp.ingest_rounds(1, [(REC_APPEND, 0, 0, rows_of([b"a", b"b"]))],
                     [[0, 2, []]])
    assert fp.park([(0, 1)], 5.0, 1) is not None
    assert fp.park([(0, 2)], 0.02, 1) is None
    with pytest.raises(ParkRefused):
        fp.park([(0, 2)], 5.0, 2)
    assert fp.read(0, 2, None, tail_ok=True) == ([], 2)
    assert fp.read(0, 2, None) is None
    assert fp.read(1, 0, None, tail_ok=True) is None  # no floor: refuse
    assert fp.stats()["reads_at_tail"] == 1 and fp.parked() == 0


# ------------------------------------------------- the sender's floors

class Wire:
    """A transport that keeps the frames it was handed."""

    def __init__(self) -> None:
        self.frames: list[dict] = []

    def call(self, addr, req, timeout=None):
        self.frames.append(req)
        return {"ok": True}


def test_which_frames_carry_a_floor_and_that_it_cannot_pass_its_gaps():
    """A pushed floor rides a records-less frame when nothing is queued;
    the read barrier's empty frame carries none; a frame with records
    carries its slots' floors and the pushed ones; the stamp is what
    `floors_fn` reads at SEND time - floor and gaps in one call - not
    what the release saw; without a `floors_fn` a push sends nothing."""
    wire = Wire()
    state = {"floors": {0: 8, 1: 8}, "gaps": {0: [], 1: []}}
    calls = []

    def floors_fn(slots):
        calls.append(list(slots))
        return [[s, state["floors"][s], [list(g) for g in state["gaps"][s]]]
                for s in slots]

    rep = RoundReplicator(
        wire, lambda b: f"b{b}", epoch_fn=lambda: 1,
        members_fn=lambda: (1,), active_fn=lambda: True,
        metrics=Metrics(), floors_fn=floors_fn)
    try:
        rep.replicate([], timeout_s=5.0)  # the read barrier's frame
        assert wire.frames[-1]["records"] == []
        assert "floors" not in wire.frames[-1]
        # the plane moves on between the release and the send: the
        # stamp must be the pair as it stands at the send
        state["floors"][0], state["gaps"][0] = 24, [[8, 16]]
        rep.push_floor([0])
        wait_until(lambda: len(wire.frames) == 2, timeout=5.0,
                   interval=0.001)
        f = wire.frames[-1]
        assert f["records"] == [] and f["floors"] == [[0, 24, [[8, 16]]]]
        assert abs(time.monotonic_ns() - f["floor_t_ns"]) < 5e9
        assert calls == [[0]]  # one pass for floor and gaps together
        rep.replicate([(REC_APPEND, 1, 8, b"r" * SB)], timeout_s=5.0)
        f = wire.frames[-1]
        assert f["floors"] == [[1, 8, []]] and "floor_t_ns" not in f
        m = rep._c_floor_frames.n, rep._c_frames.n
        assert m == (1, 2)  # a floor frame is no group commit
    finally:
        rep.stop()
    plain = RoundReplicator(
        wire, lambda b: f"b{b}", epoch_fn=lambda: 1,
        members_fn=lambda: (1,), active_fn=lambda: True)
    try:
        n = len(wire.frames)
        plain.push_floor([0, 1])
        plain.replicate([(REC_APPEND, 0, 0, b"r" * SB)], timeout_s=5.0)
        assert len(wire.frames) == n + 1
        assert set(wire.frames[-1]) == {"type", "epoch", "sender", "sseq",
                                        "records"}
    finally:
        plain.stop()


# ------------------------------------------- the rack-aware consumer

P = 4
RACKS = ((0, "a"), (1, "b"), (2, "c"))


def rack_cluster(**kw):
    return InProcCluster(make_config(
        n_brokers=3, topics=(Topic(T, P, 3),),
        engine=small_engine(partitions=P, replicas=3, slots=256,
                            max_consumers=16, read_batch=8),
        metadata_election_timeout_s=0.6, follower_reads=True,
        standby_count=2, broker_racks=RACKS, rpc_timeout_s=2.0, **kw))


def drain(cons: ConsumerClient, got: dict, want: dict,
          timeout: float = 20.0) -> None:
    deadline = time.monotonic() + timeout
    while any(len(got[p]) < len(want[p]) for p in want):
        assert time.monotonic() < deadline, {
            p: (len(got[p]), len(want[p])) for p in want}
        for p in want:
            got[p] += cons.consume(T, p)
        time.sleep(0.002)


def test_rack_consumer_reads_what_a_leader_served_one_reads():
    """One log, two consumers: `client_rack` of a standby, and none. Byte
    for byte the same, in order, each message once - with the follower
    serving before, across its death (the leader serves), and across a
    controller change."""
    with rack_cluster() as c:
        c.wait_for_leaders()
        boot = [b.address for b in c.config.brokers]
        ctl = c.controller_id()
        wait_until(lambda: len(
            c.brokers[ctl].manager.current_follower_leases()) == 2,
            timeout=10.0)
        fid = next(b for b, _ in RACKS if b != ctl)
        rack = dict(RACKS)[fid]
        # the map is advertised beside the leases
        meta = c.client("m").call(boot[0], {"type": "meta.topics"})
        assert meta["broker_racks"] == {str(b): r for b, r in RACKS}
        prod = ProducerClient(boot, transport=c.client("p"))
        kw = dict(prefetch=1, long_poll_s=0.2, max_messages=8,
                  metadata_refresh_s=0.3)
        racked = ConsumerClient(boot, "racked", transport=c.client("cr"),
                                client_rack=rack, **kw)
        plain = ConsumerClient(boot, "plain", transport=c.client("cp"), **kw)
        sent = {p: [] for p in range(P)}
        got_r = {p: [] for p in range(P)}
        got_p = {p: [] for p in range(P)}
        n = 0

        def produce(rounds: int) -> None:
            nonlocal n
            for k in range(rounds):
                p = k % P
                msgs = [b"m-%d" % (n + i) for i in range(3)]
                n += 3
                for attempt in range(40):
                    try:
                        prod.produce_batch(T, msgs, partition=p)
                        break
                    except Exception:
                        time.sleep(0.1)
                else:
                    raise AssertionError("produce never landed")
                sent[p] += msgs

        produce(12)
        drain(racked, got_r, sent)
        drain(plain, got_p, sent)
        assert got_r == got_p == sent
        served = racked.follower_served
        assert served > 0 and plain.follower_served == 0
        follower = c.brokers[fid]
        m = follower.metrics.snapshot()["counters"]
        assert m["follower.fetch_answered"] > 0
        assert m["follower.floors"] > 0
        assert follower.follower_plane.stats()["answers_past_floor"] == 0
        assert c.brokers[ctl].metrics.snapshot()["counters"][
            "repl.floor_frames"] > 0

        # the follower dies: never an error to the caller, the leader
        # serves, nothing missing and nothing twice
        c.kill(fid)
        produce(8)
        drain(racked, got_r, sent)
        drain(plain, got_p, sent)
        assert got_r == got_p == sent
        assert racked.follower_served == served

        # a controller change, with the follower back in the set
        c.restart(fid)
        wait_until(lambda: len(
            c.brokers[ctl].manager.current_standbys()) == 2, timeout=30.0)
        c.kill(ctl)
        wait_until(lambda: c.controller_id() not in (None, ctl),
                   timeout=20.0)
        c.wait_for_leaders()
        produce(8)
        drain(racked, got_r, sent, timeout=30.0)
        drain(plain, got_p, sent, timeout=30.0)
        assert got_r == got_p == sent
        racked.close()
        plain.close()
        prod.close()
