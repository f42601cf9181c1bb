"""Observability (admin.stats RPC + logging) and the timing knobs.

The reference's observability is a configured log4j2 console stack
(reference: mq-broker/src/main/resources/log4j2.xml:10-14) and nothing
else; this framework adds a stats/health RPC on every broker. The timing
knobs (election_timeout_s, metadata_election_timeout_s,
membership_poll_s) must all be LIVE — changing them changes behavior.
"""

from __future__ import annotations

import logging
import time

import pytest

from ripplemq_tpu.broker.dataplane import DataPlane
from ripplemq_tpu.broker.manager import PartitionManager
from ripplemq_tpu.broker.server import BrokerServer
from ripplemq_tpu.wire.transport import InProcNetwork
from tests.broker_harness import InProcCluster, make_config
from tests.helpers import wait_until


# ---------------------------------------------------------------- admin.stats

def test_admin_stats_surface():
    """Every broker answers admin.stats; the controller reports engine
    counters and per-slot detail, frontends report engine=None; both see
    the same controller/topics picture."""
    with InProcCluster(make_config(3)) as c:
        c.wait_for_leaders()
        client = c.client()
        ctrl = next(b for b in c.brokers.values() if b.is_controller)
        front = next(b for b in c.brokers.values() if not b.is_controller)

        # Traffic so the counters are nonzero.
        resp = client.call(
            ctrl.addr,
            {"type": "produce", "topic": "topic1", "partition": 0,
             "messages": [b"s1", b"s2"]},
            timeout=10.0,
        )
        if not resp.get("ok"):  # leader may be a frontend; follow the hint
            resp = client.call(
                resp["leader_addr"],
                {"type": "produce", "topic": "topic1", "partition": 0,
                 "messages": [b"s1", b"s2"]},
                timeout=10.0,
            )
        assert resp["ok"], resp

        stats = client.call(ctrl.addr, {"type": "admin.stats", "slots": [0]},
                            timeout=5.0)
        assert stats["ok"]
        assert stats["controller"]["is_self"]
        # Boot health is part of the surface: a healthy boot shows zero
        # consecutive failures (r5 — boot-retry loops must be
        # operator-visible, not log-only).
        assert stats["boot_failures"] == 0
        assert stats["engine"]["mirror_gap_slots"] == 0
        assert stats["engine"]["rounds"] >= 1
        assert stats["engine"]["committed_entries"] >= 2
        assert stats["engine"]["slots"]["0"]["commit"] >= 2
        assert stats["engine"]["slots"]["0"]["log_end"] >= 2
        # All partitions have elected leaders, visible in the stats.
        for t in stats["topics"].values():
            for a in t.values():
                assert a["leader"] is not None and a["term"] >= 1

        fstats = client.call(front.addr, {"type": "admin.stats"}, timeout=5.0)
        assert fstats["ok"]
        assert fstats["engine"] is None
        assert fstats["controller"]["id"] == stats["controller"]["id"]


def test_admin_stats_shows_new_leader_after_broker_death():
    """VERDICT next-#6 'done' bar: a failover's new-leader election is
    visible through admin.stats (leader moved, term bumped).

    Leaders collocate on the controller wherever its replica is
    up-to-date (manager.plan_elections), so a non-controller leader —
    the victim this test needs — only exists for partitions whose
    replica set EXCLUDES the controller; enough partitions over 4
    brokers at RF 3 guarantees at least one."""
    from ripplemq_tpu.metadata.models import Topic

    topics = (Topic("topic1", 4, 3), Topic("topic2", 2, 3))
    with InProcCluster(make_config(4, topics=topics)) as c:
        c.wait_for_leaders()
        client = c.client()
        any_b = next(iter(c.brokers.values()))
        ctrl_id = any_b.manager.current_controller()
        meta_leader = next(
            i for i, b in c.brokers.items() if b.runner.node.role == "leader"
        )
        before = client.call(any_b.addr, {"type": "admin.stats"}, timeout=5.0)
        candidates = [
            (tname, int(p), a["leader"], a["term"])
            for tname, t in before["topics"].items()
            for p, a in t.items()
            if a["leader"] not in (None, ctrl_id)
        ]
        # Prefer a victim that is not also the metadata leader (kills one
        # role at a time; double-role death is covered by the controller
        # failover suite).
        candidates.sort(key=lambda x: x[2] == meta_leader)
        assert candidates, before["topics"]
        tname, pid, victim, old_term = candidates[0]
        c.net.set_down(c.brokers[victim].addr)
        c.brokers[victim].stop()
        survivor = next(b for i, b in c.brokers.items() if i != victim)

        def healed():
            s = client.call(survivor.addr, {"type": "admin.stats"},
                            timeout=5.0)
            a = s["topics"][tname][str(pid)]
            return a["leader"] not in (None, victim) and a["term"] > old_term

        assert wait_until(healed, timeout=60), client.call(
            survivor.addr, {"type": "admin.stats"}, timeout=5.0
        )["topics"]


# -------------------------------------------------------------------- logging

def test_leader_election_and_duty_errors_are_logged(caplog):
    caplog.set_level(logging.INFO, logger="ripplemq")
    with InProcCluster(make_config(3)) as c:
        c.wait_for_leaders()
        # Metadata leadership logged by hostraft.
        assert any(
            "metadata leader at term" in r.message
            for r in caplog.records if r.name == "ripplemq.hostraft"
        )
        # Duty failures are logged (not just ring-buffered): break one
        # broker's duty and watch the warning.
        b = next(iter(c.brokers.values()))

        def boom():
            raise RuntimeError("duty-test-explosion")

        b._standby_duty = boom
        assert wait_until(
            lambda: any(
                "duty-test-explosion" in r.message
                for r in caplog.records if r.name == "ripplemq.broker"
            ),
            timeout=10,
        )
        assert any("duty-test-explosion" in e for e in b.duty_errors)


# ---------------------------------------------------------------------- knobs

def test_election_timeout_debounces_dataplane_elections():
    """election_timeout_s gates how long a partition must stay leaderless
    before the controller ballots it — and 0 disables the debounce."""
    def planner(timeout_s):
        config = make_config(3, election_timeout_s=timeout_s)
        m = PartitionManager(0, config)
        dp = DataPlane(config.engine, mode="local")
        m.attach_dataplane(dp)
        cmd = m.plan_assignment([0, 1, 2])
        assert cmd is not None
        m.apply(1, cmd)
        return m

    slow = planner(30.0)
    cands, _ = slow.plan_elections()
    assert not cands  # freshly leaderless: debounced

    fast = planner(0.0)
    cands, drafts = fast.plan_elections()
    assert cands and drafts  # no debounce: ballots immediately

    # And the debounce expires: a short timeout elects after the wait.
    short = planner(0.15)
    assert not short.plan_elections()[0]
    time.sleep(0.2)
    assert short.plan_elections()[0]


def test_metadata_election_timeout_sets_hostraft_ticks():
    """metadata_election_timeout_s drives the hostraft election deadline
    (randomized in [1x, 2x] of the timeout, in ticks)."""
    net = InProcNetwork()
    config = make_config(3, metadata_election_timeout_s=1.0)
    s = BrokerServer(0, config, net=net, tick_interval_s=0.05)
    assert s.runner.node._election_ticks == (20, 40)
    config2 = make_config(3, metadata_election_timeout_s=0.5)
    s2 = BrokerServer(1, config2, net=net, tick_interval_s=0.05)
    assert s2.runner.node._election_ticks == (10, 20)


def test_membership_poll_gates_liveness_reaction():
    """membership_poll_s is the metadata leader's planning cadence: with a
    long poll, a broker death is NOT acted on between polls (the default
    test config's 0.2 s poll heals in well under a second —
    tests/test_failover.py)."""
    config = make_config(3, membership_poll_s=30.0)
    with InProcCluster(config) as c:
        c.wait_for_leaders()  # bootstrap assignment = the first poll
        victim = next(
            i for i, b in c.brokers.items()
            if b.runner.node.role != "leader" and not b.is_controller
        )
        c.net.set_down(c.brokers[victim].addr)
        c.brokers[victim].stop()
        time.sleep(1.5)  # >> liveness horizon (0.6 s), << poll period
        survivor = next(b for i, b in c.brokers.items() if i != victim)
        assert victim in survivor.manager.live  # not re-planned yet


# ===================================================== telemetry plane (obs/)

# The admin.stats SCHEMA LOCK, ISSUE 10 edition: the expected key sets
# are DERIVED from the emit sites (ripplelint's stats_schema rule —
# analysis/stats_schema.py walks _handle_stats, settle_stats, and the
# group summary ASTs), not hand-maintained here. The division of labor:
# lint fails any emitted key that is undocumented in the README schema
# section (so a new field is a deliberate two-surface change), and THIS
# test asserts the LIVE RPC response matches the derived sets exactly
# (so a dynamically-added key the AST cannot see — or a key emitted
# only on some branch — still fails tier-1 instead of silently widening
# the schema).
from ripplemq_tpu.analysis.stats_schema import derive_schema

_SCHEMA = derive_schema()
STATS_TOP_KEYS = set(_SCHEMA.top)
STATS_ENGINE_KEYS = set(_SCHEMA.engine)
STATS_SETTLE_KEYS = set(_SCHEMA.settle)
STATS_GROUP_KEYS = set(_SCHEMA.group)


def test_admin_stats_schema_lock():
    with InProcCluster(make_config(3)) as c:
        c.wait_for_leaders()
        client = c.client()
        ctrl = next(b for b in c.brokers.values() if b.is_controller)
        front = next(b for b in c.brokers.values() if not b.is_controller)
        stats = client.call(ctrl.addr, {"type": "admin.stats"}, timeout=5.0)
        assert set(stats) == STATS_TOP_KEYS, (
            f"admin.stats top-level schema drifted: "
            f"{set(stats) ^ STATS_TOP_KEYS}"
        )
        assert set(stats["engine"]) == STATS_ENGINE_KEYS, (
            f"admin.stats engine schema drifted: "
            f"{set(stats['engine']) ^ STATS_ENGINE_KEYS}"
        )
        assert set(stats["engine"]["settle"]) == STATS_SETTLE_KEYS
        # The device block reports what the engine runs on — here the
        # CPU test platform, where the write phase is the XLA scatter
        # and one device holds all three replicas of the local binding.
        device = stats["engine"]["device"]
        assert set(device) == {"platform", "device_kind", "device_count",
                               "append_backend", "mesh", "replica_devices",
                               "peak_bytes_in_use", "programs_loaded",
                               "programs_built"}
        # No program store in a process pinned to the CPU backend.
        assert (device["programs_loaded"], device["programs_built"]) == (0, 0)
        assert device["platform"] == "cpu"
        assert device["append_backend"] == "xla"
        assert device["mesh"] is None
        assert len(device["replica_devices"]) == 3
        assert len({tuple(d) for d in device["replica_devices"]}) == 1
        # In-memory round stores (no data dir) never run the native writer.
        assert stats["store_native"] is False
        assert set(stats["metadata"]) == {"role", "term", "leader_hint"}
        assert set(stats["controller"]) == {"id", "epoch", "standbys",
                                            "is_self"}
        # Group entries are exact-keyed too (empty dict when no groups
        # exist; populated shape pinned by registering one member).
        assert stats["groups"] == {}
        assert isinstance(stats["producer_ids"], int)
        assert stats["dirty_consumer_slots"] == []
        # Striped-replication surface (ISSUE 9): a full-copy cluster
        # advertises the mode with an empty holder map and zero
        # rebuilds; value shapes pinned here, striped values by
        # tests/test_stripes.py.
        assert stats["stripe_mode"] == "full"
        assert stats["stripe_holders"] == [] or all(
            isinstance(b, int) for b in stats["stripe_holders"]
        )
        assert stats["stripe_rebuilds"] == 0
        resp = client.call(
            ctrl.addr,
            {"type": "group.join", "group": "schema-g", "member": "m0",
             "topics": ["topic1"]},
            timeout=10.0,
        )
        assert resp["ok"], resp
        stats = client.call(ctrl.addr, {"type": "admin.stats"},
                            timeout=5.0)
        assert set(stats["groups"]) == {"schema-g"}
        assert set(stats["groups"]["schema-g"]) == STATS_GROUP_KEYS
        assert stats["groups"]["schema-g"]["generation"] == 1
        assert stats["groups"]["schema-g"]["members"] == ["m0"]
        # `slots` is additive (request-gated), not schema drift.
        detail = client.call(ctrl.addr,
                             {"type": "admin.stats", "slots": [0]},
                             timeout=5.0)
        assert set(detail["engine"]) == STATS_ENGINE_KEYS | {"slots"}
        assert set(detail["engine"]["slots"]["0"]) == {"commit", "log_end",
                                                       "trim"}
        fstats = client.call(front.addr, {"type": "admin.stats"},
                             timeout=5.0)
        assert set(fstats) == STATS_TOP_KEYS and fstats["engine"] is None


def test_admin_metrics_and_trace_surface():
    """admin.metrics and admin.trace answer on every broker; traffic
    moves the produce/settle counters and appends round-lifecycle
    events; the trace window is seq-ordered and `last`-clippable."""
    with InProcCluster(make_config(3)) as c:
        c.wait_for_leaders()
        client = c.client()
        ctrl = next(b for b in c.brokers.values() if b.is_controller)
        resp = client.call(
            ctrl.addr,
            {"type": "produce", "topic": "topic1", "partition": 0,
             "messages": [b"m1", b"m2", b"m3"]},
            timeout=10.0,
        )
        if not resp.get("ok"):
            resp = client.call(
                resp["leader_addr"],
                {"type": "produce", "topic": "topic1", "partition": 0,
                 "messages": [b"m1", b"m2", b"m3"]},
                timeout=10.0,
            )
        assert resp["ok"], resp

        m = client.call(ctrl.addr, {"type": "admin.metrics"}, timeout=5.0)
        assert m["ok"] and m["obs"] is True
        counters = m["metrics"]["counters"]
        hists = m["metrics"]["histograms"]
        assert counters["produce.messages"] >= 3
        assert counters["produce.submits"] >= 1
        # The settle-stage decomposition is live: every stage histogram
        # observed at least the produced round.
        for stage in ("engine.dispatch_us", "settle.commit_wait_us",
                      "settle.standby_ack_us", "settle.persist_us",
                      "settle.release_us"):
            assert hists[stage]["count"] >= 1, stage
            assert hists[stage]["p99"] >= hists[stage]["p50"]
        # Replication group-commit telemetry on the sender.
        assert counters["repl.records"] >= 1
        assert hists["repl.group_rounds"]["count"] >= 1
        # Process-global codec frame stats (InProc transports encode for
        # wire fidelity, so they count here too).
        assert m["wire"]["enabled"] and m["wire"]["encode_frames"] > 0

        t = client.call(ctrl.addr, {"type": "admin.trace"}, timeout=5.0)
        assert t["ok"]
        types = [e["type"] for e in t["trace"]]
        for needed in ("set_leader", "dispatch", "commit", "settle_enter",
                       "settle_release"):
            assert needed in types, (needed, types)
        seqs = [e["seq"] for e in t["trace"]]
        assert seqs == sorted(seqs)
        clipped = client.call(ctrl.addr, {"type": "admin.trace", "last": 3},
                              timeout=5.0)
        assert len(clipped["trace"]) == 3
        assert clipped["trace"][-1]["seq"] == seqs[-1]

        # Frontends serve the surfaces too (broker-level slice).
        front = next(b for b in c.brokers.values() if not b.is_controller)
        fm = client.call(front.addr, {"type": "admin.metrics"}, timeout=5.0)
        assert fm["ok"] and "metrics" in fm


def test_obs_knob_disables_metrics_not_trace():
    """ClusterConfig.obs=False swaps in no-op metrics (admin.metrics
    reports enabled=False, zero counters) while the flight recorder
    keeps recording — the documented A/B contract."""
    from ripplemq_tpu.wire import codec as _codec

    try:
        with InProcCluster(make_config(3, obs=False)) as c:
            c.wait_for_leaders()
            client = c.client()
            ctrl = next(b for b in c.brokers.values() if b.is_controller)
            resp = client.call(
                ctrl.addr,
                {"type": "produce", "topic": "topic1", "partition": 0,
                 "messages": [b"x"]},
                timeout=10.0,
            )
            if not resp.get("ok"):
                resp = client.call(
                    resp["leader_addr"],
                    {"type": "produce", "topic": "topic1", "partition": 0,
                     "messages": [b"x"]},
                    timeout=10.0,
                )
            assert resp["ok"], resp
            m = client.call(ctrl.addr, {"type": "admin.metrics"},
                            timeout=5.0)
            assert m["ok"] and m["obs"] is False
            assert m["metrics"]["enabled"] is False
            assert m["metrics"]["counters"] == {}
            assert m["metrics"]["histograms"] == {}
            # The flight recorder stays ON: lifecycle events recorded.
            t = client.call(ctrl.addr, {"type": "admin.trace"}, timeout=5.0)
            types = {e["type"] for e in t["trace"]}
            assert "dispatch" in types and "set_leader" in types
            # And the postmortem still carries the full engine section
            # (its data is plane state, not registry state).
            pm = client.call(ctrl.addr, {"type": "admin.postmortem"},
                             timeout=10.0)
            assert pm["ok"] and pm["engine"]["counters"]["dispatches"] >= 1
    finally:
        # obs=False silences the PROCESS-global codec stats; restore for
        # the rest of the test session.
        _codec.enable_stats(True)


# ------------------------------------------------------- registry unit tests


def test_metrics_registry_units():
    from ripplemq_tpu.obs.metrics import Metrics

    ticks = [0.0]

    def fake_clock():
        ticks[0] += 0.001  # 1 ms per read
        return ticks[0]

    m = Metrics(clock=fake_clock)
    c = m.counter("c")
    c.inc()
    c.inc(4)
    assert m.counter("c") is c and c.n == 5
    g = m.gauge("g")
    g.set(17)
    h = m.histogram("h")
    # Log2 bucketing: 100 us lands in [64, 128) -> quantile reads 128.
    h.observe(100e-6)
    assert h.count == 1 and h.quantile(0.5) == 128
    for _ in range(99):
        h.observe(100e-6)
    h.observe(3.0)  # one 3 s outlier
    s = h.summary()
    assert s["count"] == 101
    assert s["p50"] == 128 and s["p90"] == 128
    assert s["max"] == 3_000_000
    snap = m.snapshot()
    assert snap["counters"] == {"c": 5}
    assert snap["gauges"] == {"g": 17}
    assert snap["histograms"]["h"]["count"] == 101
    # Disabled registry: same API, no state, shared null objects.
    off = Metrics(enabled=False)
    off.counter("x").inc(1000)
    off.histogram("y").observe(1.0)
    assert off.snapshot() == {"enabled": False, "counters": {},
                              "gauges": {}, "histograms": {}}


def test_flight_recorder_ring_wraps_and_clips():
    from ripplemq_tpu.obs.trace import FlightRecorder

    ticks = [0.0]

    def fake_clock():
        ticks[0] += 1.0
        return ticks[0]

    r = FlightRecorder(capacity=16, clock=fake_clock)
    for i in range(40):
        r.record("e", i=i)
    snap = r.snapshot()
    assert len(snap) == 16  # ring capacity, oldest overwritten
    assert [e["i"] for e in snap] == list(range(24, 40))
    assert [e["seq"] for e in snap] == sorted(e["seq"] for e in snap)
    assert [e["t"] for e in snap] == sorted(e["t"] for e in snap)
    clipped = r.snapshot(last=4)
    assert [e["i"] for e in clipped] == [36, 37, 38, 39]
    assert r.snapshot(last=0) == []  # not the whole ring ([-0:] trap)


def test_obs_overhead_smoke():
    """Tier-1 floor on the telemetry hot paths, on a FAKE clock so the
    measured wall time is pure bookkeeping (no perf_counter jitter in
    the observed values; the wall timer brackets the whole loop). The
    floors are far below a healthy host's rate (counters measure
    millions/s, trace hundreds of thousands/s) — they catch a
    pathological regression (an accidental lock, an O(n) snapshot on
    the write path), not a slow CI minute."""
    import time as _time

    from ripplemq_tpu.obs.metrics import Metrics
    from ripplemq_tpu.obs.trace import FlightRecorder

    m = Metrics(clock=lambda: 0.0)
    c = m.counter("hot")
    h = m.histogram("hot_us")
    n = 200_000
    t0 = _time.perf_counter()
    for _ in range(n):
        c.inc()
    counter_rate = n / (_time.perf_counter() - t0)
    t0 = _time.perf_counter()
    for _ in range(n):
        h.observe_int(123)
    hist_rate = n / (_time.perf_counter() - t0)
    r = FlightRecorder(capacity=1024, clock=lambda: 0.0)
    nr = 50_000
    t0 = _time.perf_counter()
    for i in range(nr):
        r.record("dispatch", seq=i, rounds=1, slots=2)
    trace_rate = nr / (_time.perf_counter() - t0)
    assert counter_rate > 250_000, f"counter inc at {counter_rate:.0f}/s"
    assert hist_rate > 250_000, f"histogram observe at {hist_rate:.0f}/s"
    assert trace_rate > 100_000, f"trace append at {trace_rate:.0f}/s"


# --------------------------------------------------- postmortem (admin RPC)


def test_postmortem_reconstructs_term_skew_signature():
    """ISSUE 5 acceptance: the PR 4 device-term-skew wedge signature —
    control-table term BEHIND the device current_term, nonzero
    dispatches, zero commits on the wedged slot — reconstructed from
    `admin.postmortem` output ALONE (no reach-ins, no debugger). The
    wedge recipe is tests/test_term_skew.py's: a device election whose
    OP_SET_LEADER advert never lands. The PR 4 self-heal would repair
    the wedge within seconds (tests/test_term_skew.py proves that), so
    the controller duty's election gate is frozen after bootstrap —
    this test is about DIAGNOSIS of the persisting state, not repair."""
    from ripplemq_tpu.metadata.models import Topic

    config = make_config(
        3, topics=(Topic("t", 1, 3),),
        metadata_election_timeout_s=0.6,
    )
    with InProcCluster(config) as c:
        c.wait_for_leaders()
        client = c.client()
        ctrl_id = next(iter(c.brokers.values())).manager.current_controller()
        ctrl = c.brokers[ctrl_id]
        dp = ctrl.dataplane
        assert dp is not None
        # Freeze the self-heal (needs_elections drives the duty's
        # plan_elections pass): the wedge must persist for diagnosis.
        ctrl.manager.needs_elections = lambda: False
        a = ctrl.manager.assignment_of(("t", 0))
        leader_slot = int(dp.leader[0])

        def pm_engine():
            pm = client.call(ctrl.addr, {"type": "admin.postmortem"},
                             timeout=15.0)
            assert pm["ok"], pm
            return pm["engine"]

        eng = pm_engine()
        assert eng["term_skew_slots"] == []
        commit_before = eng["device_commit"][0]

        # Fabricate the wedge: the device grants a higher term, the
        # advert is lost (we never propose OP_SET_LEADER).
        skew_term = a.term + 3
        won = dp.elect({0: (leader_slot, skew_term)})
        assert won[0]
        dispatches_before = dp.dispatches
        # Rounds now dispatch at the stale table term and are refused.
        import pytest as _pytest

        from ripplemq_tpu.broker.dataplane import NotCommittedError
        with _pytest.raises(NotCommittedError):
            dp.submit_append(0, [b"wedged"]).result(timeout=30)

        eng = pm_engine()
        # The signature, from the bundle alone:
        assert eng["term_skew_slots"] == [0]
        assert eng["ctrl_table"]["term"][0] < eng["device_current_terms"][0]
        assert eng["device_current_terms"][0] == skew_term
        assert eng["counters"]["dispatches"] > dispatches_before
        assert eng["device_commit"][0] == commit_before  # zero new commits
        assert eng["stall_streaks"].get("0", 0) >= dp.max_retry_rounds
        # And the flight recorder holds the causal history: the election
        # that bumped the device term, then dispatches with no
        # settle_release for the wedged rounds.
        pm = client.call(ctrl.addr, {"type": "admin.postmortem"},
                         timeout=15.0)
        types = [e["type"] for e in pm["trace"]]
        assert "elect" in types and "dispatch" in types


def test_postmortem_settled_gaps_and_settle_window():
    """The bundle carries the read-safety state PR 4 built (settled
    gaps) and the settle-window occupancy — checked against the plane's
    own accessors on a quiet cluster."""
    with InProcCluster(make_config(3)) as c:
        c.wait_for_leaders()
        client = c.client()
        ctrl = next(b for b in c.brokers.values() if b.is_controller)
        dp = ctrl.dataplane
        with dp._lock:
            dp._add_settled_gap_locked(1, 8, 16)
        pm = client.call(ctrl.addr, {"type": "admin.postmortem"},
                         timeout=15.0)
        eng = pm["engine"]
        assert eng["settled_gaps"] == {"1": [[8, 16]]}
        assert eng["settle"]["window"] == dp.settle_window
        assert eng["retry_budget"]["max_retry_rounds"] == dp.max_retry_rounds
        # The gap creation is also a trace event.
        types = [e["type"] for e in pm["trace"]]
        assert "settled_gap" in types


# ------------------------------------------------------------- JSON logging


def test_configure_logging_json_lines():
    """The structured mode: one JSON object per record with broker id,
    subsystem, level, thread, and message as fields (what the proc
    chaos backend launches its subprocess brokers with)."""
    import io
    import json as _json

    from ripplemq_tpu.utils.logs import configure_logging, get_logger

    buf = io.StringIO()
    try:
        configure_logging("INFO", stream=buf, json_lines=True, broker_id=7)
        get_logger("dataplane").info("hello %s", "world")
        get_logger("broker").warning("trouble at %d", 42)
        lines = [ln for ln in buf.getvalue().splitlines() if ln]
        assert len(lines) == 2
        docs = [_json.loads(ln) for ln in lines]
        assert docs[0]["subsystem"] == "dataplane"
        assert docs[0]["broker"] == 7
        assert docs[0]["level"] == "INFO"
        assert docs[0]["msg"] == "hello world"
        assert docs[0]["thread"]
        assert isinstance(docs[0]["ts"], float)
        assert docs[1]["subsystem"] == "broker"
        assert docs[1]["level"] == "WARNING"
        assert docs[1]["msg"] == "trouble at 42"
    finally:
        # Restore the default pattern for the rest of the session.
        configure_logging("WARNING")


# ------------------------------------------------------- prometheus exposition

def test_metrics_text_exposition_lock():
    """The Prometheus exposition is GENERIC over the registry the same
    way stats_schema locks admin.stats: every live counter, gauge, and
    histogram must appear in render_prometheus output with the right
    type line and suffix discipline — so a metric added anywhere in the
    codebase can never silently miss the scrape surface. Values are
    cross-checked against the snapshot the same registry serves."""
    import re

    from ripplemq_tpu.obs.metrics import Metrics, render_prometheus

    m = Metrics(enabled=True)
    m.counter("produce.messages").inc(7)
    m.gauge("settle.inflight").set(3)
    h = m.histogram("produce.ack_us")
    for v in (1, 1, 5, 5000):
        h.observe_int(v)
    text = render_prometheus(m)
    snap = m.snapshot()

    # Schema lock: every registry metric has a TYPE line + samples.
    for name, val in snap["counters"].items():
        pn = "ripplemq_" + re.sub(r"[^0-9a-zA-Z_]", "_", name)
        assert f"# TYPE {pn}_total counter" in text, name
        assert f"{pn}_total {val}" in text, name
    for name, val in snap["gauges"].items():
        pn = "ripplemq_" + re.sub(r"[^0-9a-zA-Z_]", "_", name)
        assert f"# TYPE {pn} gauge" in text, name
        assert f"{pn} {val}" in text, name
    for name, hs in snap["histograms"].items():
        pn = "ripplemq_" + re.sub(r"[^0-9a-zA-Z_]", "_", name)
        assert f"# TYPE {pn} histogram" in text, name
        assert f'{pn}_bucket{{le="+Inf"}} {hs["count"]}' in text, name
        assert f'{pn}_count {hs["count"]}' in text, name

    # Bucket discipline: cumulative, le bounds are the log2 bins'
    # inclusive upper bounds (2^i - 1), sum/count match the feed.
    buckets = re.findall(
        r'ripplemq_produce_ack_us_bucket\{le="(\d+)"\} (\d+)', text)
    les = [int(a) for a, _ in buckets]
    cums = [int(b) for _, b in buckets]
    assert les == sorted(les) and cums == sorted(cums)
    assert all((le + 1) & le == 0 for le in les), les  # 2^i - 1
    assert cums[-1] <= 4
    assert f"ripplemq_produce_ack_us_sum {1 + 1 + 5 + 5000}" in text
    assert "ripplemq_produce_ack_us_count 4" in text

    # Disabled registry: empty exposition, not a crash.
    assert render_prometheus(Metrics(enabled=False)) == ""


def test_admin_metrics_text_surface():
    """admin.metrics_text answers on every broker with the exposition
    under "text"; after traffic the produce counters are present, and a
    frontend serves its own (broker-level) registry too."""
    with InProcCluster(make_config(3)) as c:
        c.wait_for_leaders()
        client = c.client()
        ctrl = next(b for b in c.brokers.values() if b.is_controller)
        resp = client.call(
            ctrl.addr,
            {"type": "produce", "topic": "topic1", "partition": 0,
             "messages": [b"m1", b"m2"]}, timeout=10.0)
        if not resp.get("ok"):
            resp = client.call(
                resp["leader_addr"],
                {"type": "produce", "topic": "topic1", "partition": 0,
                 "messages": [b"m1", b"m2"]}, timeout=10.0)
        assert resp["ok"], resp
        t = client.call(ctrl.addr, {"type": "admin.metrics_text"},
                        timeout=5.0)
        assert t["ok"] and isinstance(t["text"], str)
        assert "# TYPE ripplemq_produce_messages_total counter" in t["text"]
        assert "ripplemq_produce_ack_us_bucket" in t["text"]
        front = next(b for b in c.brokers.values() if not b.is_controller)
        ft = client.call(front.addr, {"type": "admin.metrics_text"},
                         timeout=5.0)
        assert ft["ok"] and "# TYPE" in ft["text"]


# ------------------------------------------------ host stages (ISSUE 24)


def _half_second_clock():
    """A fake clock that advances 0.5 s per read (exact in binary, so
    sums of differences are exact) and remembers, per reading thread,
    the first and last value it handed out."""
    import threading as _threading

    ticks = [0.0]
    seen: dict[int, list[float]] = {}
    guard = _threading.Lock()

    def clock():
        with guard:
            ticks[0] += 0.5
            t = ticks[0]
            span = seen.setdefault(_threading.get_ident(), [t, t])
            span[1] = t
        return t

    return clock, seen


ROUND_STAGE_HISTOGRAMS = ("round.idle_us", "round.coalesce_us",
                          "round.drain_us", "round.lock_wait_us",
                          "engine.dispatch_us")


def test_stage_lap_partitions_a_threads_time():
    """StageLap: each boundary is ONE clock read shared by the stage it
    closes and the one it opens, so the stages' sums add up to the
    elapsed time exactly, whatever the order and repetition."""
    from ripplemq_tpu.obs.metrics import Metrics

    clock, _ = _half_second_clock()
    m = Metrics(clock=clock)
    a, b = m.stage("round.drain"), m.stage("round.launch",
                                           "engine.dispatch_us")
    only_annotated = m.stage("round.fetch", None)
    lap = m.lap()
    t_first = lap.to(a)
    for st in (b, a, a, only_annotated, b):
        clock()  # reads inside a stage do not break the closure
        lap.to(st)
    t_last = lap.to(None)
    h = m.snapshot()["histograms"]
    assert set(h) == {"round.drain_us", "engine.dispatch_us"}
    total_us = sum(m.histogram(n).total for n in h)
    # round.fetch keeps no histogram: its 1.0 s is the only part of the
    # elapsed time that no sum holds.
    assert total_us == int((t_last - t_first - 1.0) * 1e6)
    assert h["round.drain_us"]["count"] == 3
    # timed(): one region, its own object per use.
    with a.timed():
        pass
    assert m.histogram("round.drain_us").count == 4


def test_step_thread_round_stages_add_up_to_elapsed_time():
    """ISSUE 24: the five round.* stages of DataPlane._run partition the
    step thread's time — on the fake clock their sums equal the time
    between the thread's first and last clock read exactly — and
    produce.queue_wait_us holds one observation per drained pending.
    PR 32: the gather is many short laps up to a deadline counted from
    the previous launch's start, each its own round.coalesce
    observation, and the closure holds across them. (PR 51: behind a
    round that is out; a released one ends the gather in a lap.)"""
    from ripplemq_tpu.broker.dataplane import DataPlane
    from ripplemq_tpu.obs.metrics import Metrics
    from tests.helpers import small_cfg
    from tests.test_gather import hold_rounds_out

    clock, seen = _half_second_clock()
    m = Metrics(clock=clock)
    dp = DataPlane(small_cfg(), mode="local", max_retry_rounds=3,
                   metrics=m, coalesce_s=100.0)
    hold_rounds_out(dp)
    dp.start()
    try:
        dp.set_leader(0, 0, 1)
        dp.set_leader(1, 1, 1)
        # One lone message first: a quiet plane launches it at once,
        # and what follows gathers until 100 s after that launch began
        # - on a clock that gains half a second a read, some eighty
        # laps, each a real sleep of one slice.
        dp.submit_append(0, [b"m0"]).result(timeout=30)
        # (fewer than max_batch 8: that many pendings end a gather)
        futs = [dp.submit_append(i % 2, [b"m%d" % i]) for i in range(1, 6)]
        for f in futs:
            f.result(timeout=30)
        step_ident = dp._thread.ident
    finally:
        dp.stop()
    first, last = seen[step_ident]
    sums = {n: m.histogram(n).total for n in ROUND_STAGE_HISTOGRAMS}
    assert sum(sums.values()) == int((last - first) * 1e6), sums
    # Every stage ran: the plane gathered in more laps than it made
    # launches, drained, waited for the lock, launched and idled.
    counts = {n: m.histogram(n).count for n in ROUND_STAGE_HISTOGRAMS}
    assert all(counts.values()), counts
    assert counts["round.coalesce_us"] > dp.dispatches, counts
    assert counts["round.lock_wait_us"] == counts["engine.dispatch_us"] \
        == dp.dispatches
    snap = m.snapshot()
    # No round failed, so every pending was drained exactly once.
    assert snap["counters"]["produce.round_retries"] == 0
    assert snap["histograms"]["produce.queue_wait_us"]["count"] == 6
    assert snap["counters"]["round.h2d_bytes"] > 0
    assert snap["counters"].get("round.pipeline_full", 0) == 0


def test_stage_helper_is_free_when_metrics_are_off():
    """Under Metrics(enabled=False) the stage helper reads no clock and
    allocates nothing — the obs=False arm sheds the stages whole."""
    import gc
    import tracemalloc

    from ripplemq_tpu.obs.metrics import Metrics
    from ripplemq_tpu.obs.stages import NULL_LAP, NULL_STAGE

    def boom():
        raise AssertionError("a disabled registry read its clock")

    off = Metrics(enabled=False, clock=boom)
    st = off.stage("round.drain")
    lap = off.lap()
    assert st is NULL_STAGE and lap is NULL_LAP
    assert off.stage("round.fetch", None) is NULL_STAGE
    loop = [None] * 1000  # a range() would allocate its own ints
    for _ in loop[:16]:  # warm any lazily-built interpreter state
        with st.timed():
            pass
        lap.to(st)
    gc.collect()
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in loop:
        with st.timed():
            pass
        lap.to(st)
        lap.to(None)
    used = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    # Nothing PER CALL: the only live bytes after 1000 rounds are the
    # interpreter's own one-off objects (a cached bound `__exit__`, the
    # int this subtraction made), far under one byte a call.
    assert used < 256, f"disabled stage path allocated {used} bytes"
    assert off.snapshot()["histograms"] == {}


def test_stages_land_in_a_profiler_trace(tmp_path):
    """The second clock: a stage open while a jax.profiler session runs
    is an event of the same name on its thread's host line — what lets
    a device idle gap be named by the program's stage instead of by
    whichever runtime event happened to be open."""
    import jax

    from ripplemq_tpu.obs.metrics import Metrics

    m = Metrics()
    drain = m.stage("round.drain")
    fetch = m.stage("round.fetch", None)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        lap = m.lap()
        lap.to(drain)
        lap.to(fetch)
        lap.to(None)
        with drain.timed():
            pass
    finally:
        jax.profiler.stop_trace()
    paths = list(tmp_path.rglob("*.xplane.pb"))
    assert paths, "the profiler wrote no trace"
    data = jax.profiler.ProfileData.from_file(str(paths[0]))
    names = [ev.name for plane in data.planes for line in plane.lines
             for ev in line.events]
    assert names.count("round.drain") == 2
    assert names.count("round.fetch") == 1
    assert m.histogram("round.drain_us").count == 2


# ------------------------------------------- named waits (ISSUE 41)
# A traced broker reads a stage's CPU beside its wall, times three
# locks by role and runs a wake-up probe; untraced, none of it exists.


class _CountedClock:
    """A hand-moved clock that counts its reads."""

    def __init__(self):
        self.now = 0.0
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.now


def test_stage_lap_observes_cpu_beside_wall_for_the_stages_that_ask():
    """On fake clocks: a stage built with cpu=True under a waits-on
    registry observes `<stage>_cpu_us` over the same boundaries as its
    wall histogram, the CPU sum never exceeds the wall sum, and a stage
    without the flag observes no CPU series."""
    from ripplemq_tpu.obs.metrics import Metrics

    wall, cpu = _CountedClock(), _CountedClock()
    m = Metrics(clock=wall, waits=True, cpu_clock=cpu)
    drain = m.stage("round.drain", cpu=True)
    launch = m.stage("round.launch", "engine.dispatch_us", cpu=True)
    idle = m.stage("round.idle")
    lap = m.lap()
    # (stage, wall seconds in it, CPU seconds of them)
    plan = [(idle, 0.5, 0.0), (drain, 0.25, 0.125), (launch, 0.5, 0.0625),
            (idle, 1.0, 0.0), (drain, 0.125, 0.125), (launch, 0.25, 0.25)]
    for st, w, c in plan:
        lap.to(st)
        wall.now += w
        cpu.now += c
    lap.to(None)
    h = m.snapshot()["histograms"]
    assert set(h) == {"round.drain_us", "round.drain_cpu_us",
                      "engine.dispatch_us", "round.launch_cpu_us",
                      "round.idle_us"}
    total = {n: m.histogram(n).total for n in h}
    assert total["round.drain_us"] == 375_000
    assert total["round.drain_cpu_us"] == 250_000
    assert total["engine.dispatch_us"] == 750_000
    assert total["round.launch_cpu_us"] == 312_500
    for wall_name, cpu_name in (("round.drain_us", "round.drain_cpu_us"),
                                ("engine.dispatch_us",
                                 "round.launch_cpu_us")):
        assert total[cpu_name] <= total[wall_name]
        assert h[cpu_name]["count"] == h[wall_name]["count"] == 2
    # One CPU read a boundary that has a CPU stage on either side, none
    # between two stages that did not ask (idle -> None at the end had
    # launch before it, so every boundary here but none is counted).
    assert cpu.reads == len(plan)
    # timed(): the same pair from one region.
    with drain.timed():
        wall.now += 0.5
        cpu.now += 0.25
    assert m.histogram("round.drain_cpu_us").total == 500_000
    # The settle thread's CPU-only stage: no histogram of its own.
    rel = m.stage("settle.release", None, annotate=False, cpu=True)
    with rel.timed():
        wall.now += 1.0
        cpu.now += 0.5
    assert m.histogram("settle.release_cpu_us").total == 500_000
    assert "settle.release_us" not in m.snapshot()["histograms"]


@pytest.mark.parametrize("kwargs", [
    {"enabled": False, "waits": True},   # a disabled registry
    {"enabled": True, "waits": False},   # an untraced broker's
    {"enabled": True},                   # the default
])
def test_no_cpu_clock_is_read_where_waits_are_off(kwargs):
    from ripplemq_tpu.obs.metrics import Metrics
    from ripplemq_tpu.obs.stages import NULL_STAGE, stage_open

    cpu = _CountedClock()
    m = Metrics(cpu_clock=cpu, **kwargs)
    assert m.cpu_clock is None
    drain = m.stage("round.drain", cpu=True)
    launch = m.stage("round.launch", "engine.dispatch_us", cpu=True)
    # With nothing to do the CPU-only stage is the null stage.
    assert m.stage("settle.release", None, annotate=False,
                   cpu=True) is NULL_STAGE
    lap = m.lap()
    for st in (drain, launch, drain, None):
        lap.to(st)
        assert not stage_open()  # nothing marks the thread either
    with drain.timed():
        pass
    assert cpu.reads == 0
    assert not any(n.endswith("_cpu_us")
                   for n in m.snapshot()["histograms"])


def test_waits_on_laps_mark_the_thread_while_an_annotation_is_open():
    from ripplemq_tpu.obs.metrics import Metrics
    from ripplemq_tpu.obs.stages import stage_open

    m = Metrics(waits=True, cpu_clock=lambda: 0.0)
    drain = m.stage("round.drain", cpu=True)
    quiet = m.stage("read.serve", annotate=False)
    lap = m.lap()
    assert not stage_open()
    lap.to(drain)
    assert stage_open()
    lap.to(quiet)
    assert not stage_open()
    lap.to(drain)
    lap.to(None)
    assert not stage_open()


def test_wake_probe_observes_lateness_on_a_fake_clock():
    """lateness = (time it ran again) - (time it asked to); past the
    threshold, one interp_stall event."""
    from ripplemq_tpu.obs.metrics import Metrics
    from ripplemq_tpu.obs.trace import EVENT_TYPES, FlightRecorder
    from ripplemq_tpu.obs.wakeprobe import PERIOD_S, STALL_S, WakeProbe

    clock = _CountedClock()
    lates = [0.0, 0.002, 0.25, 0.001]
    stop_after = [len(lates)]

    def wait(seconds):
        assert seconds == PERIOD_S
        if not stop_after[0]:
            return True  # stop() was called during the sleep
        stop_after[0] -= 1
        clock.now += seconds + lates[len(lates) - stop_after[0] - 1]
        return False

    m, rec = Metrics(clock=clock), FlightRecorder()
    probe = WakeProbe(m, rec, wait=wait)
    probe._run()  # on this thread: ends when wait() reports the stop
    h = m.histogram("interp.wake_late_us")
    assert h.count == len(lates)
    assert h.total == pytest.approx(sum(lates) * 1e6, abs=len(lates))
    assert h.max == pytest.approx(250_000, abs=1)
    events = rec.snapshot()
    assert [e["type"] for e in events] == ["interp_stall"]
    assert events[0]["late_ms"] == pytest.approx(250.0, abs=0.01)
    assert STALL_S < 0.25 and "interp_stall" in EVENT_TYPES


def test_wake_probe_thread_stops_on_stop():
    from ripplemq_tpu.obs.metrics import Metrics
    from ripplemq_tpu.obs.trace import FlightRecorder
    from ripplemq_tpu.obs.wakeprobe import WakeProbe

    m = Metrics()
    probe = WakeProbe(m, FlightRecorder(), period_s=0.001)
    assert not probe.alive()
    probe.start()
    assert wait_until(lambda: m.histogram("interp.wake_late_us").count >= 3,
                      timeout=10.0)
    assert probe.alive()
    probe.stop()
    assert not probe.alive()
    probe.stop()  # idempotent


@pytest.mark.parametrize("name", [
    "lock.wait_us.DataPlane._lock.rpc",
    "lock.wait_us.PartitionManager.lock.rpc",
    "lock.hold_us.DataPlane._lock.step",
    "lock.hold_us.DataPlane._lock.settle",
    "lock.wait_us.DataPlane._lock.step",
    "lock.hold_us.DataPlane._device_lock.step",
    "round.drain_cpu_us", "round.launch_cpu_us", "settle.release_cpu_us",
    "interp.wake_late_us",
])
def test_new_series_are_found_by_the_benchmarks_reader_names(name):
    """The exposition's spelling of a new histogram is the one
    `benchmarks/readers/_common.series_name` asks for, and no two of the
    new names collapse into one series (`._` of a lock's name and the
    dots survive `_prom_name` unambiguously)."""
    from benchmarks.readers._common import parse_exposition, series_name
    from ripplemq_tpu.obs import lockwitness as lw
    from ripplemq_tpu.obs.metrics import Metrics, render_prometheus

    m = Metrics()
    all_names = [f"lock.{kind}_us.{lock}.{role}"
                 for kind in ("wait", "hold")
                 for lock in sorted(lw.TIMED_LOCKS) for role in lw.ROLES]
    all_names += ["round.drain_cpu_us", "round.launch_cpu_us",
                  "settle.release_cpu_us", "interp.wake_late_us",
                  "round.drain_us", "engine.dispatch_us"]
    assert name in all_names
    for i, n in enumerate(all_names):
        m.histogram(n).observe_int(i + 1)
    values = parse_exposition(render_prometheus(m))
    assert len({series_name(n, "_sum") for n in all_names}) \
        == len(all_names)
    i = all_names.index(name)
    assert values[series_name(name, "_sum")] == i + 1
    assert values[series_name(name, "_count")] == 1


def test_new_layer_metric_files_read_what_the_instruments_observe():
    """Each of the thirteen metric files of ISSUE 41, read with its
    reader from a registry the instruments' names were observed into;
    on a program without them (the parent) the reader finds nothing and
    does not raise; file and BENCHMARK.json entry agree."""
    import importlib
    import json
    import os

    from benchmarks.readers._common import parse_exposition
    from ripplemq_tpu.obs.metrics import Metrics, render_prometheus

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {e["name"]: e for e in bench["per_layer"]}
    e2e = {e["name"]: e for e in bench["end_to_end"]}
    m = Metrics()
    before = parse_exposition(render_prometheus(m))
    feed = {"lock.wait_us.DataPlane._lock.rpc": [0, 0, 300],
            "lock.wait_us.PartitionManager.lock.rpc": [0, 900],
            "lock.hold_us.DataPlane._lock.step": [2000, 4000],
            "lock.hold_us.DataPlane._lock.settle": [500],
            "lock.wait_us.DataPlane._lock.step": [1000],
            "round.drain_us": [4000, 4000], "round.drain_cpu_us": [2000],
            "engine.dispatch_us": [5000], "round.launch_cpu_us": [1000],
            "interp.wake_late_us": [100, 300]}
    for name, values in feed.items():
        for v in values:
            m.histogram(name).observe_int(v)
    after = parse_exposition(render_prometheus(m))
    run = {"t0_ns": 100, "t1_ns": 200,
           "snapshots": [(101, before), (199, after)]}
    old = {"t0_ns": 100, "t1_ns": 200,
           "snapshots": [(101, before), (199, before)]}
    want = {"plane_lock_wait_rpc_ms": 0.1, "manager_lock_wait_rpc_ms": 0.45,
            "plane_lock_hold_step_ms": 3.0, "plane_lock_hold_settle_ms": 0.5,
            "drain_lock_share": 0.125, "drain_cpu_share": 0.25,
            "launch_cpu_share": 0.2, "interp_wake_late_ms": 0.2}
    saturate = {"plane_lock_wait_rpc_ms", "manager_lock_wait_rpc_ms",
                "drain_lock_share", "launch_cpu_share",
                "interp_wake_late_ms"}
    names = [f"host.{k}" for k in want] + [f"saturate.{k}"
                                           for k in sorted(saturate)]
    assert len(names) == 13
    for name in names:
        path = os.path.join(root, "benchmarks", "layer_metrics",
                            f"{name}.json")
        with open(path) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']['kind']}")
        got = reader.read(spec["reader"]["args"], run)
        assert got == pytest.approx(want[name.split(".", 1)[1]]), name
        assert reader.read(spec["reader"]["args"], old) is None, name
        entry = entries[name]
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        # the file names the cells that were there before the metric; a
        # cell that came after lists the metric in its own file, and the
        # entry holds both
        assert set(spec["workloads"]) <= set(entry["workloads"]), name
        assert set(entry["workloads"]) <= set(
            e2e[spec["moves"]]["workloads"])
        assert (spec["moves"] == "acked_msgs_per_s") \
            == name.startswith("saturate.")


@pytest.mark.parametrize("sample_n", [0, 1])
def test_waits_hang_off_trace_sample_n_alone(sample_n):
    """Untraced: the three locks of a booted controller are raw
    `threading` locks, its registry has no CPU clock and no probe thread
    exists. Traced: timing wrappers, a CPU clock, a probe - and the
    series reach admin.metrics_text; stop() takes all of it down."""
    import threading

    from ripplemq_tpu.obs import lockwitness as lw

    def probes():
        return [t for t in threading.enumerate() if t.name == "wake-probe"]

    assert not lw.timing_enabled() and not probes()
    cfg = make_config(3, obs=True, trace_sample_n=sample_n)
    with InProcCluster(cfg) as c:
        c.wait_for_leaders()
        ctrl = next(b for b in c.brokers.values() if b.is_controller)
        dp = ctrl.dataplane
        three = (dp._lock, dp._device_lock, ctrl.manager.lock)
        if not sample_n:
            assert type(dp._lock) is type(threading.Lock())
            assert type(dp._device_lock) is type(threading.Lock())
            assert type(ctrl.manager.lock) is type(threading.RLock())
            assert ctrl.metrics.cpu_clock is None
            assert not lw.timing_enabled() and not probes()
        else:
            assert [type(x) for x in three] == [
                lw.TimedLock, lw.TimedLock, lw.TimedRLock]
            # Each broker's locks observe into its OWN registry.
            assert all(x._sink.metrics is ctrl.metrics for x in three)
            assert ctrl.metrics.cpu_clock is time.thread_time
            assert len(probes()) == len(c.brokers)
            client = c.client()
            resp = client.call(
                ctrl.addr, {"type": "produce", "topic": "topic1",
                            "partition": 0, "messages": [b"m1"]},
                timeout=10.0)
            if not resp.get("ok"):
                resp = client.call(
                    resp["leader_addr"],
                    {"type": "produce", "topic": "topic1", "partition": 0,
                     "messages": [b"m1"]}, timeout=10.0)
            assert resp["ok"], resp
            text = client.call(ctrl.addr, {"type": "admin.metrics_text"},
                               timeout=5.0)["text"]
            for series in (
                    "ripplemq_lock_hold_us_DataPlane__lock_step_count",
                    "ripplemq_lock_wait_us_PartitionManager_lock_other_sum",
                    "ripplemq_lock_hold_us_DataPlane__device_lock_step_sum",
                    "ripplemq_round_drain_cpu_us_sum",
                    "ripplemq_round_launch_cpu_us_count",
                    "ripplemq_settle_release_cpu_us_count",
                    "ripplemq_interp_wake_late_us_count"):
                assert series in text, series
            h = ctrl.metrics.histogram
            assert h("lock.hold_us.DataPlane._lock.step").count > 0
            assert h("round.launch_cpu_us").count \
                == h("engine.dispatch_us").count > 0
            # CPU and wall come from two clocks (thread_time, the
            # registry's), read a few instructions apart and each cut
            # to a whole microsecond: a stage that was all CPU (a first
            # launch on a quiet machine) reads a few us OVER its wall
            # (2849 against 2846 in the driver's run of PR 41's tree).
            # What the pair must not do is drift: 20 us a stage.
            for cpu, wall in (("round.launch_cpu_us", "engine.dispatch_us"),
                              ("round.drain_cpu_us", "round.drain_us")):
                assert h(cpu).total <= h(wall).total + 20 * h(wall).count
    assert not lw.timing_enabled()
    assert wait_until(lambda: not probes(), timeout=5.0)
