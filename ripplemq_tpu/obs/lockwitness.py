"""Runtime lock witness: record ACTUAL per-thread lock-acquisition
orderings, off the hot path unless asked for.

The static lock-order graph (`analysis/lock_graph.py`) derives "lock A
is held while lock B is acquired" edges from the AST — but the AST
cannot see through function-valued indirection (`replicate_wait_fn`,
duck-typed replicator planes) or runtime dispatch. This module closes
that static/dynamic gap the same way `stats_schema` closes
emit-site/doc drift: every host-path lock is created through the named
factories below, and when the witness is ENABLED each acquisition is
recorded against the acquiring thread's currently-held set. The chaos
smokes then assert two things about the witnessed graph:

- it is ACYCLIC (a witnessed cycle is a deadlock that simply has not
  scheduled yet), and
- it is CONTAINED in the static graph's transitive closure — a
  witnessed edge the AST missed means the static analysis lost
  coverage through an indirection, and the run FAILS so the edge gets
  derived or declared (lock_graph.DECLARED_EDGES) rather than silently
  unchecked.

Gating: `enabled()` is a process-global flag. The factories return RAW
`threading.Lock`/`RLock`/`Condition` objects while disabled — zero
wrapper, zero overhead, nothing to reason about in production. Enabling
(`enable()`, or `ClusterConfig.lock_witness: true` at broker boot)
affects locks created AFTER the call, so harnesses enable before
constructing the cluster (chaos `run_chaos(lock_witness=True)`,
`profiles/chaos_soak.py --witness`). Names passed to the factories are
the static graph's node ids (`ClassName.attr`); `analysis/lock_graph.py`
lints that every factory call site's name literal matches the attribute
it is assigned to, so the two planes cannot drift apart.

TIMING MODE (beside the witness, independent of it): a traced broker
(`trace_sample_n > 0`) calls `enable_timing(metrics, recorder)` before
it builds its locks, and the factories then hand out a timing wrapper
for a CLOSED set of three names (`TIMED_LOCKS`: the two convoy locks
every handler takes and the device lock) and exactly what they hand out
otherwise for every other name. Per acquisition the wrapper observes
`lock.wait_us.<lock>.<role>` (call of `acquire` to its return; 0 for an
uncontended take, so the count is the number of acquisitions) and
`lock.hold_us.<lock>.<role>` (acquire's return to the release; for the
RLock the owner's OUTERMOST pair only; a `Condition.wait` ends the hold
and the re-acquire after it is a wait), keyed by the ROLE of the
thread, read once from its name (`thread_role`), and both observed
after the release: the wrapper keeps out of the hold it times (`_Timed`). A
contended wait of a thread with a stage annotation open (the step,
resolver and settle threads; never an RPC worker) is itself a
`jax.profiler.TraceAnnotation` `lock.wait:<lock>`, nested in that
stage, so it never names a device gap; a hold past `LONG_HOLD_S`
leaves one `lock_long_hold` event in the flight recorder. With timing
off none of this exists: the factories return the raw `threading`
primitives. Witness and timing compose: the timing wrapper's inner
lock is the witness wrapper, so the witnessed edges are the witness's
own.
"""

from __future__ import annotations

import sys
import threading
from typing import Optional

_enabled = False
# (held_name, acquired_name) -> count of distinct observations. Guarded
# by _REG_LOCK on first insertion; reads ride the GIL (dict membership
# is atomic) so the recording fast path takes no lock once an edge is
# known.
_edges: dict[tuple[str, str], int] = {}
_names_seen: set[str] = set()
_REG_LOCK = threading.Lock()
_tls = threading.local()


def enable() -> None:
    """Turn the witness on for locks created from now on."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Drop every recorded edge (harnesses call between runs)."""
    with _REG_LOCK:
        _edges.clear()
        _names_seen.clear()


def _held() -> list:
    h = getattr(_tls, "held", None)
    if h is None:
        h = _tls.held = []
    return h


def _note_acquire(name: str) -> None:
    held = _held()
    if held:
        for h in held:
            if h == name:
                # Same NAME already held: either RLock depth (handled
                # by the wrapper) or a sibling instance of the same
                # class lock — instance-blind by design, so no
                # self-edge (a name-level self-edge would read every
                # cross-broker in-proc acquisition as a deadlock).
                continue
            key = (h, name)
            # The count is part of the verdict: exact, under the
            # registry lock (the witness polices unguarded RMWs — it
            # does not get to commit one; debug-mode cost, measured in
            # PROFILE.md).
            with _REG_LOCK:
                _edges[key] = _edges.get(key, 0) + 1
    if name not in _names_seen:
        with _REG_LOCK:
            _names_seen.add(name)
    held.append(name)


def _note_release(name: str) -> None:
    held = _held()
    # Locks release out of acquisition order legitimately (hand-over-
    # hand), so drop the LAST occurrence of this name, not the top.
    for i in range(len(held) - 1, -1, -1):
        if held[i] == name:
            del held[i]
            return


class WitnessLock:
    """threading.Lock wrapper recording acquisition-order edges. Also a
    valid Condition(lock): `_release_save`/`_acquire_restore`/`_is_owned`
    mirror CPython's plain-lock fallbacks so Condition.wait() correctly
    pops the held entry for the wait window (wait RELEASES the lock —
    orderings observed inside the window must not claim it was held)."""

    __slots__ = ("_inner", "name")

    def __init__(self, name: str, inner=None) -> None:
        self._inner = inner if inner is not None else threading.Lock()
        self.name = name

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._inner.acquire(blocking, timeout)
        if got:
            _note_acquire(self.name)
        return got

    def release(self) -> None:
        _note_release(self.name)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    # -- Condition(lock) protocol (CPython fallback semantics) --

    def _release_save(self):
        _note_release(self.name)
        self._inner.release()

    def _acquire_restore(self, _saved) -> None:
        self._inner.acquire()
        _note_acquire(self.name)

    def _is_owned(self) -> bool:
        if self._inner.acquire(False):
            self._inner.release()
            return False
        return True


class WitnessRLock:
    """threading.RLock wrapper; reentrant depth tracked so nested
    acquisitions by the owner record one held entry, no self-edges.
    Implements the Condition(lock) protocol by delegating to the inner
    RLock's own `_release_save`/`_acquire_restore`/`_is_owned` (which
    fully release/restore the recursion count) so a witnessed
    Condition keeps raw `threading.Condition()` semantics — including
    REENTRANCY of the condition's mutex."""

    __slots__ = ("_inner", "name", "_owner", "_depth")

    def __init__(self, name: str) -> None:
        self._inner = threading.RLock()
        self.name = name
        self._owner: Optional[int] = None
        self._depth = 0

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._inner.acquire(blocking, timeout)
        if got:
            me = threading.get_ident()
            if self._owner == me:
                self._depth += 1
            else:
                self._owner = me
                self._depth = 1
                _note_acquire(self.name)
        return got

    def release(self) -> None:
        if self._depth > 1:
            self._depth -= 1
        else:
            self._depth = 0
            self._owner = None
            _note_release(self.name)
        self._inner.release()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    # -- Condition(lock) protocol: wait() fully releases the recursion
    # count; the held entry pops for the whole wait window.

    def _release_save(self):
        state = self._inner._release_save()
        depth, self._depth = self._depth, 0
        self._owner = None
        _note_release(self.name)
        return (state, depth)

    def _acquire_restore(self, saved) -> None:
        state, depth = saved
        self._inner._acquire_restore(state)
        self._owner = threading.get_ident()
        self._depth = depth
        _note_acquire(self.name)

    def _is_owned(self) -> bool:
        return self._inner._is_owned()


# ------------------------------------------------------------ timing mode

# The CLOSED set of timed names: the two locks every RPC handler takes
# (the convoy PERF.md keeps finding) and the device lock.
TIMED_LOCKS = frozenset({
    "DataPlane._lock", "PartitionManager.lock", "DataPlane._device_lock",
})
# A hold longer than this leaves one `lock_long_hold` event in the
# flight recorder (site taken at release, on that slow path only).
LONG_HOLD_S = 0.05
# Roles of the controller's threads, by thread-name prefix; anything
# else is "other" (raft pump/io, store flusher and erasure worker,
# warm-up, SLO controller, catch-up, the main thread).
ROLES = ("step", "resolve", "settle", "read", "repl", "rpc", "duty",
         "other")
_ROLE_BY_PREFIX = (
    ("dataplane-step", "step"),
    ("dataplane-resolve", "resolve"),
    ("dataplane-settle", "settle"),
    ("dataplane-read", "read"),
    ("repl-sender", "repl"),
    ("rpc-worker", "rpc"),
    ("tcp-conn", "rpc"),
    ("broker-duty", "duty"),
)


def thread_role() -> str:
    """The calling thread's role, from its name, resolved once."""
    role = getattr(_tls, "role", None)
    if role is None:
        name = threading.current_thread().name
        role = _tls.role = next(
            (r for prefix, r in _ROLE_BY_PREFIX if name.startswith(prefix)),
            "other")
    return role


class _Timing:
    """Where the timed locks of one broker observe: its registry (whose
    clock they run on) and its flight recorder."""

    __slots__ = ("metrics", "recorder", "clock", "ann_cls", "stage_open")

    def __init__(self, metrics, recorder) -> None:
        from ripplemq_tpu.obs.stages import _annotation_cls, stage_open

        self.metrics = metrics
        self.recorder = recorder
        self.clock = metrics.clock
        self.ann_cls = _annotation_cls()
        self.stage_open = stage_open

    def hists(self, name: str) -> dict:
        """role -> (wait histogram, hold histogram), every role
        resolved HERE (lock construction): the hot path must never take
        the registry's lock under the lock it times."""
        m = self.metrics
        return {
            role: (m.histogram(f"lock.wait_us.{name}.{role}"),
                   m.histogram(f"lock.hold_us.{name}.{role}"))
            for role in ROLES
        }


_timing: Optional[_Timing] = None


def enable_timing(metrics, recorder=None) -> None:
    """Time the `TIMED_LOCKS` created from now on onto `metrics` (and
    `recorder` for long holds). Process-global like the witness; a lock
    keeps the registry it was created under."""
    global _timing
    _timing = _Timing(metrics, recorder)


def disable_timing(metrics=None) -> None:
    """Turn timing off - only if `metrics` is the registry it is on
    for, when one is named (a stopping broker must not turn off a
    sibling's)."""
    global _timing
    if metrics is None or (_timing is not None
                           and _timing.metrics is metrics):
        _timing = None


def timing_enabled() -> bool:
    return _timing is not None


def _caller_site() -> str:
    """`function:line` of the nearest frame outside this module and
    `threading` (a Condition's wait releases through it)."""
    f = sys._getframe(1)
    skip = (__file__, threading.__file__)
    while f is not None and f.f_code.co_filename in skip:
        f = f.f_back
    if f is None:
        return "?"
    return f"{f.f_code.co_name}:{f.f_lineno}"


class _Timed:
    """What the two timed flavours share. The wrapper keeps OUT of the
    hold it times: on the uncontended path both clock reads sit outside
    the inner lock (one before the non-blocking try, one after the
    inner release - a hold reads a few hundred ns long), and between
    the inner acquire and the inner release the wrapper runs two stores
    and two loads and enters one Python function (`__exit__`). Every
    call or function entry under the lock is a point where the
    interpreter may hand over to another thread with the lock still
    held, which is how a convoy starts: a timed lock must not seed what
    it measures. `_t0` is the current hold's start and `_w` the wait
    that led to it, written by the holder alone; both histograms are
    observed after the release, under the role of the releasing thread
    (for these three locks always the acquirer)."""

    __slots__ = ("_inner", "name", "_sink", "_clock", "_hists", "_t0", "_w")

    def __init__(self, name: str, sink: _Timing, inner) -> None:
        self._inner = inner
        self.name = name
        self._sink = sink
        self._clock = sink.clock
        self._hists = sink.hists(name)
        self._t0 = 0.0
        self._w = 0.0

    def _contended(self, t0: float, blocking: bool, timeout: float) -> bool:
        """The non-blocking try made at `t0` failed: wait for the inner
        lock (as an annotation where the thread has a stage open),
        start the hold."""
        sink = self._sink
        ann = None
        if sink.ann_cls is not None and sink.stage_open():
            ann = sink.ann_cls(f"lock.wait:{self.name}")
            ann.__enter__()
        got = self._inner.acquire(blocking, timeout)
        t = self._clock()
        if got:
            self._w, self._t0 = t - t0, t
        if ann is not None:
            ann.__exit__(None, None, None)
        return got

    def _observe(self, waited: float, held: float) -> None:
        """After the inner release: the acquisition's wait and hold."""
        role = thread_role()
        wait_h, hold_h = self._hists[role]
        wait_h.observe(waited)
        hold_h.observe(held)
        if held > LONG_HOLD_S and self._sink.recorder is not None:
            self._sink.recorder.record(
                "lock_long_hold", lock=self.name, role=role,
                held_ms=round(held * 1e3, 3), site=_caller_site())


class TimedLock(_Timed):
    """threading.Lock (or its witness wrapper) with its waits and holds
    observed by role."""

    __slots__ = ()

    def __init__(self, name: str, sink: _Timing, inner=None) -> None:
        super().__init__(name, sink,
                         inner if inner is not None else threading.Lock())

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        t = self._clock()
        if self._inner.acquire(False):
            self._t0 = t
            self._w = 0.0
            return True
        if not blocking:
            return False
        return self._contended(t, blocking, timeout)

    __enter__ = acquire

    def release(self, *_exc) -> None:
        t0, waited = self._t0, self._w
        self._inner.release()
        self._observe(waited, self._clock() - t0)

    __exit__ = release

    def locked(self) -> bool:
        return self._inner.locked()

    # -- Condition(lock) protocol (CPython's plain-lock fallbacks, or
    # the witness wrapper's own): a wait ends the hold, the re-acquire
    # after it is a wait.

    def _release_save(self):
        t0, waited = self._t0, self._w
        save = getattr(self._inner, "_release_save", None)
        if save is not None:
            save()
        else:
            self._inner.release()
        self._observe(waited, self._clock() - t0)

    def _acquire_restore(self, _saved) -> None:
        t0 = self._clock()
        restore = getattr(self._inner, "_acquire_restore", None)
        if restore is not None:
            restore(None)
        else:
            self._inner.acquire()
        t = self._clock()
        self._w, self._t0 = t - t0, t

    def _is_owned(self) -> bool:
        owned = getattr(self._inner, "_is_owned", None)
        if owned is not None:
            return owned()
        if self._inner.acquire(False):
            self._inner.release()
            return False
        return True


class TimedRLock(_Timed):
    """threading.RLock (or its witness wrapper): one wait and one hold
    for the owner's OUTERMOST acquire/release (the witness's depth
    rule). A non-blocking take of a held RLock succeeds for its owner
    alone, so a nonzero depth there means re-entry."""

    __slots__ = ("_depth",)

    def __init__(self, name: str, sink: _Timing, inner=None) -> None:
        super().__init__(name, sink,
                         inner if inner is not None else threading.RLock())
        self._depth = 0

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        t = self._clock()
        if self._inner.acquire(False):
            if self._depth:
                self._depth += 1
            else:
                self._depth = 1
                self._t0 = t
                self._w = 0.0
            return True
        if not blocking:
            return False
        got = self._contended(t, blocking, timeout)
        if got:
            self._depth = 1
        return got

    __enter__ = acquire

    def release(self, *_exc) -> None:
        if self._depth > 1:
            self._depth -= 1
            self._inner.release()
            return
        t0, waited = self._t0, self._w
        self._depth = 0
        self._inner.release()
        self._observe(waited, self._clock() - t0)

    __exit__ = release

    def _release_save(self):
        t0, waited = self._t0, self._w
        depth, self._depth = self._depth, 0
        state = self._inner._release_save()
        self._observe(waited, self._clock() - t0)
        return (state, depth)

    def _acquire_restore(self, saved) -> None:
        state, depth = saved
        t0 = self._clock()
        self._inner._acquire_restore(state)
        t = self._clock()
        self._depth = depth
        self._w, self._t0 = t - t0, t

    def _is_owned(self) -> bool:
        return self._inner._is_owned()


def make_lock(name: str):
    """A mutex named for the static lock graph (`ClassName.attr`).
    Disabled: a raw threading.Lock."""
    if _timing is not None and name in TIMED_LOCKS:
        return TimedLock(name, _timing,
                         WitnessLock(name) if _enabled else None)
    if not _enabled:
        return threading.Lock()
    return WitnessLock(name)


def make_rlock(name: str):
    if _timing is not None and name in TIMED_LOCKS:
        return TimedRLock(name, _timing,
                          WitnessRLock(name) if _enabled else None)
    if not _enabled:
        return threading.RLock()
    return WitnessRLock(name)


def make_condition(name: str, lock=None):
    """A condition variable whose underlying mutex is witnessed under
    `name`. Pass `lock` to share an existing (witnessed or raw) mutex —
    the Condition-aliases-its-lock idiom (`analysis/lock_graph.py`
    models the alias the same way). The standalone form wraps an RLOCK,
    because raw `threading.Condition()` defaults to one — the witness
    must never make a legal reentrant path deadlock only in debug
    mode."""
    if lock is not None:
        return threading.Condition(lock)
    if not _enabled:
        return threading.Condition()
    return threading.Condition(WitnessRLock(name))


# ------------------------------------------------------------- reporting


def edges() -> dict[tuple[str, str], int]:
    with _REG_LOCK:
        return dict(_edges)


def report(static_closure: Optional[set] = None) -> dict:
    """JSON-able witness verdict: the observed edges, acyclicity, and —
    when the static graph's transitive closure is supplied — the
    witnessed edges the AST never derived (each one is a coverage hole
    that must become a derived or declared static edge)."""
    from ripplemq_tpu.utils.graphs import cycles as _cycles

    obs = edges()
    found = _cycles(obs.keys())
    out = {
        "enabled": _enabled,
        "locks": sorted(_names_seen),
        "edges": sorted([a, b, n] for (a, b), n in obs.items()),
        "acyclic": not found,
        "cycles": found,
    }
    if static_closure is not None:
        out["uncovered_edges"] = sorted(
            [a, b] for (a, b) in obs if (a, b) not in static_closure
        )
    return out
