"""The step thread's gather (DataPlane._run; PR 32, PR 51): a round
starts when the one before it has been RELEASED, and at the latest
coalesce_s after that one STARTED, waited for in slices - not a sleep
that begins when the thread happens to look, so a launch, an
offsets-only round or a late wake-up is time gathered, not time added.

Every case runs on a clock the test moves by hand (the registry's
injectable clock), so an open gather stays open until the test says
otherwise and nothing here sleeps for a coalesce window: the plane's
own laps are one slice (4 ms) each. The deadline's cases (PR 32's) run
behind a round that is kept out (`hold_rounds_out`): with none out a
drainable append goes one slice after the launch's return, which is
what the last test of this file is about."""

import threading
import time

import pytest

from ripplemq_tpu.broker import dataplane as dataplane_mod
from ripplemq_tpu.broker.dataplane import DataPlane
from ripplemq_tpu.obs.metrics import Metrics
from tests.helpers import small_cfg, wait_until

COALESCE_S = 10.0  # on the hand-moved clock
SETTLE_S = 0.08    # real time: twenty slices of an open gather


class HandClock:
    def __init__(self, t: float = 100.0) -> None:
        self._t = t
        self._guard = threading.Lock()

    def __call__(self) -> float:
        with self._guard:
            return self._t

    def advance(self, dt: float) -> None:
        with self._guard:
            self._t += dt


class Rig:
    """A bare local plane on a hand clock, leaders set, NOT started."""

    def __init__(self, coalesce_s: float = COALESCE_S,
                 held: bool = False) -> None:
        self.clock = HandClock()
        self.metrics = Metrics(clock=self.clock)
        self.dp = DataPlane(small_cfg(), mode="local", max_retry_rounds=3,
                            metrics=self.metrics, coalesce_s=coalesce_s)
        for slot in range(4):
            self.dp.set_leader(slot, 0, 1)
        # `held`: every round stays out until the test calls `release`.
        self.release = hold_rounds_out(self.dp) if held else None

    def prime(self) -> None:
        """Start the plane and put one round behind it, launched at the
        clock's present reading: a plane that has launched nothing
        gathers for nothing."""
        self.dp.start()
        self.dp.submit_append(3, [b"prime"]).result(timeout=30)
        assert self.dp.dispatches == 1 and self.laps() == 0
        assert self.counter("round.gather_expired") == 1

    def counter(self, name: str) -> int:
        return self.metrics.snapshot()["counters"].get(name, 0)

    def laps(self) -> int:
        return self.metrics.histogram("round.coalesce_us").count

    def holds(self, dispatches: int = 1) -> bool:
        """Twenty slices on: nothing more was launched."""
        time.sleep(SETTLE_S)
        return self.dp.dispatches == dispatches

    def back(self, rounds: int) -> bool:
        """`rounds` dispatches have left the pipeline: an ack goes out
        a moment BEFORE its round is counted back."""
        return wait_until(lambda: self.dp._rounds_back == rounds,
                          timeout=30, interval=0.002)


def hold_first_launch(dp):
    """Stub the launch: the FIRST one signals `inside` and stays there
    until the test sets `leave`; every launch runs the real program."""
    inside, leave = threading.Event(), threading.Event()
    real = dp.fns.step_sparse

    def held_launch(*args):
        if not inside.is_set():
            inside.set()
            assert leave.wait(timeout=30)
        return real(*args)

    dp.fns = dp.fns._replace(step_sparse=held_launch)
    return inside, leave


def hold_rounds_out(dp):
    """Stub the rounds' way back: they settle and ack as ever, but none
    is counted back (and no gather is woken) before the test calls the
    `release` this returns - the state of a round between its launch
    and its release, for as long as a test needs it."""
    real, guard = dp._round_back, threading.Lock()
    owed: list[bool] = []
    released = []

    def held_back(windowed):
        with guard:
            if not released:
                owed.append(windowed)
                return
        real(windowed)

    def release():
        with guard:
            released.append(True)
        while owed:
            real(owed.pop())

    dp._round_back = held_back
    return release


@pytest.fixture()
def rig():
    """A rig whose rounds stay out: the deadline ends every gather."""
    r = Rig(held=True)
    yield r
    r.dp.stop()


def test_past_the_deadline_a_batch_is_drained_with_no_lap(rig):
    """(a) the previous round started coalesce_s ago: what comes now
    goes at once, with no round.coalesce lap, and round.gather_expired
    counts the loop top that found it so. A plane that has launched
    nothing is the same case (`prime`)."""
    rig.prime()
    rig.clock.advance(COALESCE_S)
    assert rig.dp.submit_append(0, [b"m0"]).result(timeout=30) == 0
    assert rig.laps() == 0
    assert rig.counter("round.gather_expired") == 2
    assert rig.counter("round.offsets_only") == 0


def test_a_launch_is_time_gathered_not_time_added(rig):
    """(b) a batch queued while the step thread is inside a launch goes
    coalesce_s after that launch STARTED: not coalesce_s after the
    thread came back (the parent's sleep: 114 here), and not later
    than coalesce_s after its own submit (113)."""
    inside, leave = hold_first_launch(rig.dp)
    rig.dp.start()
    first = rig.dp.submit_append(0, [b"a"])  # 100: a quiet plane, at once
    assert inside.wait(timeout=30)
    rig.clock.advance(3.0)                   # 103, thread still in launch
    second = rig.dp.submit_append(1, [b"b"])
    rig.clock.advance(1.0)                   # 104: the launch returns
    leave.set()
    assert first.result(timeout=30) == 0
    assert wait_until(lambda: rig.laps() > 0, timeout=30, interval=0.005)
    rig.clock.advance(5.5)                   # 109.5: half a second to go
    assert rig.holds() and not second.done()
    rig.clock.advance(0.5)                   # 110 = launch start + coalesce_s
    assert second.result(timeout=30) == 0
    wait = rig.metrics.histogram("produce.queue_wait_us")
    assert (wait.count, wait.total) == (2, int(7.0 * 1e6))


@pytest.mark.parametrize("batches,ends_at_once", [
    ([1] * 8, True),    # max_batch drainable pendings
    ([1] * 7, False),   # one short
    ([8], False),       # ONE pending of max_batch rows: pendings count
], ids=["max_batch_pendings", "one_short", "one_pending_of_max_batch_rows"])
def test_max_batch_pendings_end_the_gather(rig, batches, ends_at_once):
    """(c) today's effect, pinned: the early end compares a count of
    PENDINGS with max_batch, whatever rows they hold."""
    assert rig.dp.cfg.max_batch == 8
    rig.prime()
    futs = [rig.dp.submit_append(i % 2, [b"m%d" % i] * n)
            for i, n in enumerate(batches)]
    if ends_at_once:
        for f in futs:
            f.result(timeout=30)
    else:
        assert rig.holds() and rig.laps() > 0
        rig.clock.advance(COALESCE_S)
        for f in futs:
            f.result(timeout=30)
    # Never past a deadline without a lap: the clock stood still until
    # the gather had lapped (1 is the priming round's).
    assert rig.counter("round.gather_expired") == 1


def test_pendings_on_busy_slots_open_no_gather(rig):
    """(d) a queue behind an in-flight round cannot be drained, so it
    holds nothing back; once its slot is free the batch waits no longer
    than coalesce_s from its OWN submit, older than the last launch."""
    with rig.dp._lock:
        rig.dp._busy_a.add(0)  # white box: slot 0's round in flight
    fut = rig.dp.submit_append(0, [b"m0"])   # queued at 100
    rig.dp.start()
    rig.clock.advance(5.0)                   # 105: a launch beside it
    assert rig.dp.submit_offsets(1, [(3, 1)]).result(timeout=30) is True
    assert rig.laps() == 0 and not fut.done()
    assert rig.dp._gather_left(105.0, 105.0) is None
    with rig.dp._lock:
        rig.dp._busy_a.discard(0)
    assert rig.dp._gather_left(105.0, 105.0) == 5.0  # 100 + 10 - 105
    rig.clock.advance(5.0)
    assert fut.result(timeout=30) == 0


def test_offset_commits_ride_the_open_gathers_round(rig):
    """(e) nothing is launched inside an open gather: a commit queued
    meanwhile goes out WITH the append, in one round."""
    rig.prime()
    app = rig.dp.submit_append(0, [b"m0"])
    off = rig.dp.submit_offsets(1, [(3, 1)])
    assert rig.holds() and not off.done()
    rig.clock.advance(COALESCE_S)
    assert app.result(timeout=30) == 0 and off.result(timeout=30) is True
    assert rig.dp.rounds == 2
    assert rig.counter("round.offsets_only") == 0


def test_offsets_only_round_goes_at_once_on_a_quiet_plane(rig):
    """(f) no append to ride and no round within coalesce_s: at once,
    and the counter moves."""
    rig.dp.start()
    assert rig.dp.submit_offsets(1, [(3, 1)]).result(timeout=30) is True
    assert rig.laps() == 0
    assert rig.counter("round.offsets_only") == 1
    assert rig.counter("round.gather_expired") == 1


def test_offsets_only_round_keeps_the_cadence(rig):
    """(f') inside coalesce_s of the previous round's start a commit
    waits like anything else - an append that comes meanwhile takes it
    along - and goes alone at the deadline if none came."""
    rig.prime()
    off = rig.dp.submit_offsets(1, [(3, 1)])
    assert rig.holds() and not off.done() and rig.laps() > 0
    rig.clock.advance(COALESCE_S)
    assert off.result(timeout=30) is True
    assert rig.counter("round.offsets_only") == 1


def test_zero_coalesce_launches_with_no_lap():
    """(g) coalesce_s 0 still means no gather."""
    r = Rig(coalesce_s=0)
    try:
        r.dp.start()
        assert r.dp.submit_append(0, [b"m0"]).result(timeout=30) == 0
        assert r.dp.submit_append(1, [b"m1"]).result(timeout=30) == 0
        assert r.laps() == 0
        assert r.counter("round.gather_expired") == 0
    finally:
        r.dp.stop()


def test_stop_cuts_a_gather_short(rig, monkeypatch):
    """(h) stop() does not wait out a slice, let alone the window: with
    a slice of 20 s the step thread is gone in a fraction of one."""
    monkeypatch.setattr(dataplane_mod, "_GATHER_SLICE_S", 20.0)
    rig.prime()
    rig.dp.submit_append(0, [b"m0"])
    time.sleep(0.1)  # a lap is booked when it ends: this one has not
    assert rig.laps() == 0 and rig.dp.dispatches == 1
    t0 = time.perf_counter()
    rig.dp.stop()
    assert not rig.dp._thread.is_alive()
    assert time.perf_counter() - t0 < 5.0  # a quarter of ONE slice
    assert rig.laps() == 1


def test_one_slice_more_after_a_launch_longer_than_the_window():
    """Where the launch outlasts coalesce_s (ref-compose: 2 ms against
    a 12 ms launch) the deadline has passed when the thread comes back;
    it still gathers min(coalesce_s, one slice) from its return, for
    the requests the last round's acks set loose."""
    r = Rig(coalesce_s=0.002)
    inside, leave = hold_first_launch(r.dp)
    try:
        r.dp.start()
        first = r.dp.submit_append(0, [b"a"])
        assert inside.wait(timeout=30)
        r.clock.advance(1.0)                 # a launch of one second
        second = r.dp.submit_append(1, [b"b"])
        leave.set()
        assert first.result(timeout=30) == 0
        assert r.holds() and not second.done()
        r.clock.advance(0.002)
        assert second.result(timeout=30) == 0
        assert r.counter("round.gather_expired") == 1  # `first` alone
    finally:
        r.dp.stop()


def test_gather_runs_on_a_real_clock_when_the_registry_is_off():
    """A disabled registry's clock is a constant: a deadline read from
    it would never come, and launch stamps taken from its lap timer
    would always have passed."""
    dp = DataPlane(small_cfg(), mode="local", max_retry_rounds=3,
                   obs=False, coalesce_s=0.5)
    for slot in range(4):
        dp.set_leader(slot, 0, 1)
    hold_rounds_out(dp)  # the deadline, not the first round's release
    dp.start()
    try:
        assert dp.submit_append(0, [b"m0"]).result(timeout=30) == 0
        futs = [dp.submit_append(i % 4, [b"m%d" % i]) for i in range(1, 6)]
        for f in futs:
            f.result(timeout=30)
        assert dp.rounds == 2  # the five gathered half a second, together
    finally:
        dp.stop()


# ---- a round starts when the one before it has been released (PR 51)


def _released_round_costs_one_slice(r, monkeypatch):
    """(i) behind a RELEASED round an append goes one slice after the
    launch's return, with no coalesce_s waited."""
    r.prime()
    assert r.back(1)
    fut = r.dp.submit_append(0, [b"m0"])     # 100: launch and return
    assert r.holds() and not fut.done() and r.laps() > 0
    r.clock.advance(dataplane_mod._GATHER_SLICE_S)
    assert fut.result(timeout=30) == 0
    assert r.counter("round.gather_early") == 1
    assert r.counter("round.gather_expired") == 1  # the priming round's
    wait = r.metrics.histogram("produce.queue_wait_us")
    assert (wait.count, wait.total) == (2, 4000)


def _release_wakes_the_gather(r, monkeypatch):
    """(ii) behind an unreleased round the append waits, slice and all;
    the release ends the gather there and then - with a slice of 20 s
    real time, in a fraction of one: the settle thread wakes it."""
    monkeypatch.setattr(dataplane_mod, "_GATHER_SLICE_S", 20.0)
    r.prime()
    r.clock.advance(30.0)     # a slice past the return, 70 to go
    fut = r.dp.submit_append(0, [b"m0"])
    time.sleep(0.1)  # a lap is booked when it ends: this one has not
    assert r.laps() == 0 and r.dp.dispatches == 1 and not fut.done()
    t0 = time.perf_counter()
    r.release()
    assert fut.result(timeout=30) == 0
    assert time.perf_counter() - t0 < 5.0  # a quarter of ONE slice
    assert r.laps() == 1
    assert r.counter("round.gather_early") == 1


def _never_released_goes_at_the_deadline(r, monkeypatch):
    """(iii) a count of rounds back that never moves leaves the
    deadline to end the gather: the parent's timing."""
    r.prime()
    fut = r.dp.submit_append(0, [b"m0"])
    r.clock.advance(COALESCE_S - 0.5)
    assert r.holds() and not fut.done()
    r.clock.advance(0.5)
    assert fut.result(timeout=30) == 0
    assert r.counter("round.gather_early") == 0
    assert r.counter("round.gather_expired") == 1  # lapped: no more


def _a_commit_alone_ends_no_gather_early(r, monkeypatch):
    """(iv) behind a released round a commit still waits for the
    deadline (f'); an append that comes meanwhile goes early and takes
    it along."""
    r.prime()
    assert r.back(1)
    r.clock.advance(1.0)                     # 101: the slice is over
    off = r.dp.submit_offsets(1, [(3, 1)])
    assert r.holds() and not off.done() and r.laps() > 0
    app = r.dp.submit_append(0, [b"m0"])
    assert app.result(timeout=30) == 0 and off.result(timeout=30) is True
    assert r.dp.rounds == 2
    assert r.counter("round.offsets_only") == 0
    assert r.counter("round.gather_early") == 1
    assert r.back(2)
    r.clock.advance(1.0)                     # 102, round two from 101
    off = r.dp.submit_offsets(1, [(3, 2)])
    r.clock.advance(COALESCE_S - 1.5)
    assert r.holds(2) and not off.done()
    r.clock.advance(0.5)                     # 111 = 101 + coalesce_s
    assert off.result(timeout=30) is True
    assert r.counter("round.offsets_only") == 1
    assert r.counter("round.gather_early") == 1


def _a_window_within_a_slice_is_never_early(r, monkeypatch):
    """(v) coalesce_s no longer than a slice: one window after the
    launch's return IS the deadline, released round or not (the launch
    of a second: test_one_slice_more_after_a_launch_longer_than_the_
    window, which runs with nothing held)."""
    r.prime()
    assert r.back(1)
    fut = r.dp.submit_append(0, [b"m0"])
    assert r.holds() and not fut.done() and r.laps() > 0
    r.clock.advance(0.002)
    assert fut.result(timeout=30) == 0
    assert r.counter("round.gather_early") == 0
    assert r.counter("round.gather_expired") == 1


class _Unfetchable:
    """A `committed` output whose host fetch fails."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("fetch failed")


def _fail_the_launch(dp):
    def no_launch(*args):
        raise RuntimeError("launch failed")

    dp.fns = dp.fns._replace(step_sparse=no_launch)


def _fail_the_fetch(dp):
    real = dp.fns.step_sparse

    def launch(*args):
        state, out = real(*args)
        return state, out._replace(committed=_Unfetchable())

    dp.fns = dp.fns._replace(step_sparse=launch)


def _fail_the_settle(dp):
    def no_standby(records):
        raise RuntimeError("standby lost")

    dp.replicate_fn = no_standby


def _a_failed_round_counts_as_back(fail, out):
    """(vi) a round that ends in a step error (its launch: never handed
    on, `out` 0), a resolve error (its fetch) or a settle failure
    leaves nothing out: the next gather ends one slice after the failed
    launch's return, if it came to one."""
    def case(r, monkeypatch):
        real_fns, real_replicate = r.dp.fns, r.dp.replicate_fn
        fail(r.dp)
        r.dp.start()
        with pytest.raises(Exception, match="failed|standby lost"):
            r.dp.submit_append(3, [b"lost"]).result(timeout=30)
        assert r.back(out) and r.dp._dispatch_seq == out
        r.dp.fns, r.dp.replicate_fn = real_fns, real_replicate
        r.clock.advance(1.0)
        assert r.dp.submit_append(0, [b"m0"]).result(timeout=30) == 0
        assert r.laps() == 0
        assert r.counter("round.gather_early") == 1

    return case


@pytest.mark.parametrize("case,rig_kw", [
    (_released_round_costs_one_slice, {}),
    (_release_wakes_the_gather, {"coalesce_s": 100.0, "held": True}),
    (_never_released_goes_at_the_deadline, {"held": True}),
    (_a_commit_alone_ends_no_gather_early, {}),
    (_a_window_within_a_slice_is_never_early, {"coalesce_s": 0.002}),
    (_a_failed_round_counts_as_back(_fail_the_launch, 0), {}),
    (_a_failed_round_counts_as_back(_fail_the_fetch, 1), {}),
    (_a_failed_round_counts_as_back(_fail_the_settle, 1), {}),
], ids=["released_one_slice", "release_wakes", "never_released_deadline",
        "commit_alone_waits", "window_within_a_slice", "failed_launch",
        "failed_fetch", "failed_settle"])
def test_a_round_starts_when_the_one_before_is_released(case, rig_kw,
                                                         monkeypatch):
    r = Rig(**rig_kw)
    try:
        case(r, monkeypatch)
    finally:
        r.dp.stop()
