"""Runtime lock witness units (obs/lockwitness.py): recording
semantics, Condition-wait release accounting, cycle detection, and the
witnessed ⊆ static-closure cross-check the chaos smokes gate on.

Each test builds PRIVATE WitnessLock objects and resets the global
registry — the witness flag itself stays untouched except where a test
exercises the factory gating (restored in finally)."""

from __future__ import annotations

import threading

import pytest

from ripplemq_tpu.obs import lockwitness as lw


@pytest.fixture(autouse=True)
def _clean_registry():
    lw.reset()
    yield
    lw.reset()


def _edge_pairs():
    return set(lw.edges().keys())


def test_nested_acquisition_records_edge():
    a = lw.WitnessLock("A.x")
    b = lw.WitnessLock("B.y")
    with a:
        with b:
            pass
    assert ("A.x", "B.y") in _edge_pairs()
    assert ("B.y", "A.x") not in _edge_pairs()


def test_sequential_acquisitions_record_nothing():
    a = lw.WitnessLock("A.x")
    b = lw.WitnessLock("B.y")
    with a:
        pass
    with b:
        pass
    assert _edge_pairs() == set()


def test_every_held_lock_edges_to_the_new_one():
    a, b, c = (lw.WitnessLock(n) for n in ("A.x", "B.y", "C.z"))
    with a, b, c:
        pass
    assert {("A.x", "B.y"), ("A.x", "C.z"), ("B.y", "C.z")} <= _edge_pairs()


def test_condition_wait_releases_the_held_entry():
    """cond.wait() RELEASES the mutex: an acquisition made by another
    thread during the wait window must NOT record an edge from the
    waiting thread's condition lock — exactly why the wrapper
    implements the _release_save/_acquire_restore protocol."""
    inner = lw.WitnessLock("Plane._cond")
    cond = threading.Condition(inner)
    other = lw.WitnessLock("Other.lock")
    started = threading.Event()
    release = threading.Event()

    def waiter():
        with cond:
            started.set()
            cond.wait(timeout=5.0)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    assert started.wait(5.0)
    # While the waiter sits INSIDE wait() (lock released), this thread
    # acquires both locks nested — the only legal edge involves them.
    with other:
        with inner:
            cond.notify_all()
            release.set()
    t.join(5.0)
    pairs = _edge_pairs()
    assert ("Other.lock", "Plane._cond") in pairs
    # No edge ever claims the condition was held across the window.
    assert ("Plane._cond", "Other.lock") not in pairs


def test_rlock_reentrancy_records_no_self_edge():
    r = lw.WitnessRLock("R.lock")
    with r:
        with r:
            pass
    assert ("R.lock", "R.lock") not in _edge_pairs()


def test_report_detects_cycle():
    a = lw.WitnessLock("A.x")
    b = lw.WitnessLock("B.y")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    rep = lw.report()
    assert not rep["acyclic"]
    assert rep["cycles"] == [["A.x", "B.y"]]


def test_report_static_closure_containment():
    a = lw.WitnessLock("A.x")
    b = lw.WitnessLock("B.y")
    c = lw.WitnessLock("C.z")
    with a:
        with b:
            pass
    with a:
        with c:
            pass
    # Static graph knows A→B directly and A→C only via B (closure).
    closure = {("A.x", "B.y"), ("A.x", "C.z"), ("B.y", "C.z")}
    rep = lw.report(static_closure=closure)
    assert rep["uncovered_edges"] == []
    # Remove the transitive knowledge: A→C becomes an uncovered edge.
    rep = lw.report(static_closure={("A.x", "B.y")})
    assert rep["uncovered_edges"] == [["A.x", "C.z"]]


def test_witnessed_condition_mutex_is_reentrant():
    """Raw `threading.Condition()` defaults to an RLock; the witnessed
    standalone condition must keep that — a legal reentrant path may
    never deadlock ONLY in debug mode (review finding on this PR's
    first cut). wait() still fully releases the recursion count."""
    lw.enable()
    try:
        cond = lw.make_condition("P._cond")
    finally:
        lw.disable()
    with cond:
        with cond:  # reentrant: raw Condition allows this
            pass
    # Full-depth release across wait(): another thread can take the
    # mutex while the owner waits, even from depth 2.
    entered = threading.Event()

    def notifier():
        with cond:
            entered.set()
            cond.notify_all()

    with cond:
        with cond:
            t = threading.Thread(target=notifier, daemon=True)
            t.start()
            cond.wait(timeout=5.0)
    t.join(5.0)
    assert entered.is_set()


def test_factories_hand_out_raw_locks_while_disabled():
    assert not lw.enabled()
    assert isinstance(lw.make_lock("X.l"), type(threading.Lock()))
    assert lw.make_rlock("X.r").__class__.__name__ == "RLock"
    assert isinstance(lw.make_condition("X.c"), threading.Condition)


def test_factories_wrap_while_enabled():
    lw.enable()
    try:
        lk = lw.make_lock("X.l")
        assert isinstance(lk, lw.WitnessLock) and lk.name == "X.l"
        assert isinstance(lw.make_rlock("X.r"), lw.WitnessRLock)
        cond = lw.make_condition("X.c")
        # Standalone conditions wrap an RLOCK (raw Condition() default).
        assert isinstance(cond._lock, lw.WitnessRLock)
        # Shared-lock form keeps the caller's mutex (the
        # Condition(self._lock) alias idiom).
        shared = lw.make_lock("Y.l")
        cond2 = lw.make_condition("Y.c", lock=shared)
        assert cond2._lock is shared
    finally:
        lw.disable()


def test_witness_overhead_floor():
    """The wrapper must stay cheap enough for debug chaos runs: an
    uncontended acquire/release pair through the witness sustains a
    modest floor even on a loaded CI host (raw Lock does ~1-10M/s;
    the generous floor just catches accidental O(edges) work landing
    on the acquire path)."""
    import time

    lk = lw.WitnessLock("Bench.lock")
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with lk:
            pass
    dt = time.perf_counter() - t0
    assert n / dt > 50_000, f"witnessed acquire/release at {n/dt:.0f}/s"


def test_static_closure_covers_live_witness_names():
    """Wiring check: every witnessed factory name in the tree is a node
    the static lock graph knows (the witness_name lint enforces the
    literal matches; this asserts the graph side so a factory rename
    cannot silently detach the containment check)."""
    from ripplemq_tpu.analysis.framework import Repo
    from ripplemq_tpu.analysis.lock_graph import build_graph

    lg = build_graph(Repo())
    for name in ("DataPlane._lock", "DataPlane._device_lock",
                 "SegmentStore._lock", "RoundReplicator._lock",
                 "StripeReplicator._lock", "RaftRunner.lock",
                 "PartitionManager.lock", "BrokerServer._stamp_lock"):
        assert name in lg.locks, f"{name} missing from the static graph"


# ------------------------------------------------------------ timing mode
# (PR 41) The same factories hand out a timing wrapper for a closed set
# of three names where a traced broker asked for it: waits and holds by
# the acquiring thread's role, on the registry's clock - here a fake,
# moved by hand, with no real sleep.

from ripplemq_tpu.obs.metrics import Metrics  # noqa: E402
from ripplemq_tpu.obs.trace import EVENT_TYPES, FlightRecorder  # noqa: E402


class _Clock:
    def __init__(self):
        self.now = 0.0
        self.on_read = None  # called with the reading thread's name

    def __call__(self):
        if self.on_read is not None:
            self.on_read(threading.current_thread().name)
        return self.now


@pytest.fixture
def timed():
    clock = _Clock()
    m = Metrics(clock=clock)
    rec = FlightRecorder()
    lw.enable_timing(m, rec)
    try:
        yield m, rec, clock
    finally:
        lw.disable_timing()
        lw.disable()


def _h(m, kind, lock, role):
    return m.histogram(f"lock.{kind}_us.{lock}.{role}")


def _in_thread(name, fn):
    """Run fn on a thread of that name (its role is read from it)."""
    err = []

    def run():
        try:
            fn()
        except BaseException as e:  # surfaced on the caller's thread
            err.append(e)

    t = threading.Thread(target=run, name=name, daemon=True)
    t.start()
    return t, err


@pytest.mark.parametrize("factory,name,raw", [
    (lw.make_lock, "DataPlane._lock", type(threading.Lock())),
    (lw.make_lock, "DataPlane._device_lock", type(threading.Lock())),
    (lw.make_rlock, "PartitionManager.lock", type(threading.RLock())),
])
def test_timing_off_hands_out_the_raw_lock_types(factory, name, raw):
    assert not lw.timing_enabled() and not lw.enabled()
    assert type(factory(name)) is raw


@pytest.mark.parametrize("factory,name,wrapper", [
    (lw.make_lock, "DataPlane._lock", lw.TimedLock),
    (lw.make_lock, "DataPlane._device_lock", lw.TimedLock),
    (lw.make_rlock, "PartitionManager.lock", lw.TimedRLock),
])
def test_timing_on_wraps_the_closed_set(timed, factory, name, wrapper):
    assert name in lw.TIMED_LOCKS and len(lw.TIMED_LOCKS) == 3
    assert type(factory(name)) is wrapper


@pytest.mark.parametrize("factory,name,raw", [
    (lw.make_lock, "DataPlane._read_lock", type(threading.Lock())),
    (lw.make_lock, "Metrics._lock", type(threading.Lock())),
    (lw.make_rlock, "RaftRunner.lock", type(threading.RLock())),
])
def test_timing_on_leaves_every_other_name_raw(timed, factory, name, raw):
    assert type(factory(name)) is raw


def test_uncontended_acquire_observes_a_wait_of_zero_and_counts(timed):
    m, _, clock = timed
    lk = lw.make_lock("DataPlane._lock")
    for _ in range(3):
        with lk:
            clock.now += 0.002
    wait, hold = (_h(m, k, "DataPlane._lock", "other")
                  for k in ("wait", "hold"))
    assert (wait.count, wait.total) == (3, 0)
    assert (hold.count, hold.total) == (3, 6000)
    assert not lk.locked()


def test_wait_and_hold_land_under_the_acquiring_threads_role(timed):
    """Two named threads contend: the step thread holds for 30 ms of
    the fake clock while an RPC worker waits for it."""
    m, _, clock = timed
    lk = lw.make_lock("DataPlane._lock")
    held, waiting, go = (threading.Event() for _ in range(3))
    # The worker's first clock read is the start of its contended wait
    # (its non-blocking try has failed by then): only then may the
    # holder move the clock.
    clock.on_read = lambda who: who.startswith("rpc-") and waiting.set()

    def step():
        with lk:
            held.set()
            assert go.wait(5.0)
            clock.now += 0.030

    def rpc():
        assert held.wait(5.0)
        with lk:
            clock.now += 0.001

    ts, es = _in_thread("dataplane-step", step)
    tr, er = _in_thread("rpc-worker_7", rpc)
    assert waiting.wait(5.0)
    go.set()
    ts.join(5.0), tr.join(5.0)
    assert not es and not er, (es, er)
    assert _h(m, "hold", "DataPlane._lock", "step").total == 30_000
    assert _h(m, "wait", "DataPlane._lock", "step").total == 0
    rpc_wait = _h(m, "wait", "DataPlane._lock", "rpc")
    assert rpc_wait.count == 1 and rpc_wait.total == 30_000
    assert _h(m, "hold", "DataPlane._lock", "rpc").total == 1_000
    # Nothing under any other role.
    for role in lw.ROLES:
        if role not in ("step", "rpc"):
            assert _h(m, "wait", "DataPlane._lock", role).count == 0


@pytest.mark.parametrize("thread_name,role", [
    ("dataplane-step", "step"), ("dataplane-resolve-2", "resolve"),
    ("dataplane-settle", "settle"), ("dataplane-read", "read"),
    ("repl-sender-1", "repl"), ("rpc-worker_12", "rpc"),
    ("tcp-conn", "rpc"), ("broker-duty-0", "duty"),
    ("segstore-erasure", "other"), ("raft-pump-0", "other"),
])
def test_thread_role_is_read_from_the_threads_name(thread_name, role):
    got = []
    t, err = _in_thread(thread_name, lambda: got.append(lw.thread_role()))
    t.join(5.0)
    assert not err and got == [role] and role in lw.ROLES


def test_rlock_reentry_observes_one_wait_and_one_hold(timed):
    m, _, clock = timed
    lk = lw.make_rlock("PartitionManager.lock")
    with lk:
        clock.now += 0.001
        with lk:
            clock.now += 0.002
            with lk:
                clock.now += 0.004
        clock.now += 0.008
    wait, hold = (_h(m, k, "PartitionManager.lock", "other")
                  for k in ("wait", "hold"))
    assert (wait.count, hold.count) == (1, 1)
    assert hold.total == 15_000
    # Free again: another thread can take it.
    t, err = _in_thread("rpc-worker_0", lambda: lk.acquire() and lk.release())
    t.join(5.0)
    assert not err and not t.is_alive()
    assert _h(m, "hold", "PartitionManager.lock", "rpc").count == 1


@pytest.mark.parametrize("factory,name", [
    (lw.make_lock, "DataPlane._lock"),
    (lw.make_rlock, "PartitionManager.lock"),
])
def test_condition_wait_ends_the_hold_and_the_reacquire_is_a_wait(
        timed, factory, name):
    m, _, clock = timed
    lk = factory(name)
    cond = threading.Condition(lk)
    with cond:
        clock.now += 0.010          # first hold: 10 ms
        cond.wait(timeout=0.01)     # released, re-acquired: a second wait
        clock.now += 0.003          # second hold: 3 ms
    wait, hold = (_h(m, k, name, "other") for k in ("wait", "hold"))
    assert wait.count == 2 and hold.count == 2
    assert hold.total == 13_000
    with cond:                      # still a sound mutex afterwards
        cond.notify_all()
    assert hold.count == 3


def test_witness_and_timing_together_leave_the_witnessed_edges(timed):
    """The timing wrapper's inner lock is the witness wrapper: the
    edges are what the witness alone records for the same sequence."""
    def sequence(a, b, r):
        with a:
            with b:
                with r:
                    with r:
                        pass
        cond = threading.Condition(r)
        with cond:
            cond.wait(timeout=0.001)
            with b:
                pass

    names = ("DataPlane._lock", "DataPlane._read_lock",
             "PartitionManager.lock")
    lw.disable_timing()
    lw.enable()
    sequence(lw.make_lock(names[0]), lw.make_lock(names[1]),
             lw.make_rlock(names[2]))
    alone = lw.edges()
    lw.reset()
    m, rec, _ = timed
    lw.enable_timing(m, rec)
    a, b, r = (lw.make_lock(names[0]), lw.make_lock(names[1]),
               lw.make_rlock(names[2]))
    assert isinstance(a, lw.TimedLock) and isinstance(r, lw.TimedRLock)
    assert isinstance(a._inner, lw.WitnessLock)
    assert isinstance(b, lw.WitnessLock)  # outside the closed set
    assert isinstance(r._inner, lw.WitnessRLock)
    sequence(a, b, r)
    assert lw.edges() == alone and alone
    assert _h(m, "hold", "PartitionManager.lock", "other").count == 3


def _holder_site(lk, clock, seconds):
    with lk:
        clock.now += seconds  # the site the event must name


def test_a_long_hold_records_exactly_one_event_with_the_holders_site(timed):
    m, rec, clock = timed
    lk = lw.make_lock("DataPlane._lock")
    _holder_site(lk, clock, lw.LONG_HOLD_S / 2)   # under the threshold
    assert rec.snapshot() == []
    _holder_site(lk, clock, 0.120)
    _holder_site(lk, clock, 0.001)
    events = rec.snapshot()
    assert [e["type"] for e in events] == ["lock_long_hold"]
    e = events[0]
    assert (e["lock"], e["role"], e["held_ms"]) == (
        "DataPlane._lock", "other", 120.0)
    fn, _, line = e["site"].partition(":")
    assert fn == "_holder_site" and int(line) > 0
    assert "lock_long_hold" in EVENT_TYPES


def test_a_contended_wait_is_annotated_only_inside_an_open_stage(timed):
    """The annotation half: a contended wait opens `lock.wait:<lock>`
    only while its thread has a stage annotation open (so it nests in
    the stage and never names a device gap) - the step thread inside
    `round.drain`, not the settle thread between its stages, not an RPC
    worker, whose one stage (`read.serve`) is not annotated."""
    m, rec, clock = timed
    opened = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            pass

    waits = Metrics(clock=clock, waits=True, cpu_clock=lambda: 0.0)
    lw.enable_timing(waits, rec)
    lw._timing.ann_cls = Ann
    lk = lw.make_lock("DataPlane._lock")
    drain = waits.stage("round.drain")
    read = waits.stage("read.serve", annotate=False)

    def contend(stage):
        def run():
            lap = waits.lap()
            lap.to(stage)
            lk.acquire(True, 0.005)  # held by the main thread: times out
            lap.to(None)
        return run

    with lk:
        for name, stage in (("dataplane-step", drain),
                            ("dataplane-settle", None),
                            ("rpc-worker_1", read)):
            t, err = _in_thread(name, contend(stage))
            t.join(5.0)
            assert not err, err
    assert opened == ["lock.wait:DataPlane._lock"]


def test_timed_overhead_floor(timed):
    """An uncontended pair through the timing wrapper (one clock read,
    two observations) stays within a small factor of the witness's
    floor."""
    import time

    m = Metrics()
    lw.enable_timing(m, None)
    lk = lw.make_lock("DataPlane._lock")
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with lk:
            pass
    dt = time.perf_counter() - t0
    assert n / dt > 50_000, f"timed acquire/release at {n/dt:.0f}/s"
