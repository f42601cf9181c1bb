"""Host stages: each timed where it runs, once, onto both clocks.

A STAGE is a named stretch of one thread's time on the host path of a
round (the step thread's gather, its drain, the wait for the
device lock, the launch call, a resolver's blocking fetch, the settle
thread's standby wait, a sealed segment's RS encode, one shard's push
to its peer). Timing one does
two things at once, from the same pair of instants:

- observes the registry histogram `<name>_us` on `metrics.clock()` —
  the program's clock, the one `admin.metrics_text` and the span ring
  share — so a window's SUM says how much of the window the stage held;
- opens a `jax.profiler.TraceAnnotation` of the same name, so a
  profiler trace of the chip-owning broker shows the stage on the
  profiler's clock, on the thread's own line, next to the device's
  "XLA Ops" — an idle gap on the device can then be named by the host
  stage that overlaps it instead of by whichever C++ runtime event
  happened to be open.

Stages of one thread are SEQUENTIAL, never nested in each other: a
reduction that names a gap by the largest overlap can then pick one.
Per round or per RPC only, never per message.

Two ways to time, one vocabulary:

- `with stage.timed(): ...` — one stage around one region, usable from
  any number of threads at once (each `timed()` is a lap of its own,
  opened on the spot and closed on leaving the block).
- `lap = metrics.lap(); lap.to(stage)` — ONE thread's time PARTITIONED
  into stages: `to()` closes the open stage and opens the next on one
  clock read, so over any window the stages' sums add up to the window.
  That closure is what makes the split trustworthy (tested on the fake
  clock in tests/test_observability.py).

A stage's CPU beside its wall: a registry built with waits on
(`Metrics(waits=True)`, a traced broker) hands its laps a second clock,
`time.thread_time`, read at the same boundary as the first for the
stages that ask for it (`metrics.stage(name, cpu=True)`), observed as
`<name>_cpu_us`. Wall minus CPU is what the thread spent NOT running:
waiting for a lock, for the interpreter, for the scheduler. The same
laps mark the thread as having a stage annotation open (`stage_open`),
which is what lets a timed lock (obs/lockwitness.py) nest its
contended-wait annotation inside the stage. With waits off no CPU
clock is read and nothing is marked.

Off paths: a disabled registry (`Metrics(enabled=False)`) hands out the
`NULL_STAGE` / `NULL_LAP` singletons — no clock read, no allocation.
Where JAX is absent the annotation half is skipped and the histogram
half still works; with no profiler session open an annotation costs
well under a microsecond and records nothing.

The stage NAMES are a closed vocabulary (`STAGE_NAMES`), checked by the
same ripplelint rule as the flight recorder's events and the span kinds
(analysis/trace_vocab.py): every `.stage("<name>", ...)` site must name
a member, every member must have a site, and every member is documented
in the README "Observability" section.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

STAGE_NAMES = frozenset({
    # The step thread (broker/dataplane.py _run), a partition of its
    # time: waiting for work or for room in the resolver pipeline, the
    # gather (laps until the round before is released, at most until
    # coalesce_s after it started), building the
    # round, waiting for the device lock, the launch call (histogram:
    # engine.dispatch_us).
    "round.idle", "round.coalesce", "round.drain", "round.lock_wait",
    "round.launch",
    # A resolver's blocking fetch of the round's committed mask
    # (annotation only; settle.commit_wait_us has the number).
    "round.fetch",
    # The settle thread: the standby-ack barrier and the local persist
    # (annotations over the intervals settle.standby_ack_us /
    # settle.persist_us already time).
    "settle.standby_wait", "settle.persist",
    # One DataPlane.read call (mirror, ring or store), on whichever RPC
    # thread serves it: histogram only, no annotation.
    "read.serve",
    # One stand of a long-polling fetch on the plane (DataPlane.park):
    # registration to the settle release that ends it, or to its
    # deadline. On RPC threads: histogram only, no annotation.
    "fetch.park",
    # One sealed segment's RS encode, any compile included
    # (storage/segment.py erasure worker).
    "seal.rs_encode",
    # One shard of a sealed segment pushed to its peer by the duty loop
    # (broker/server.py _shard_duty): file read, frame encode, the
    # shard.put RPC and its answer.
    "seal.shard_put",
    # The settle thread's part of settle.release_us's interval (an
    # entry leaving the settle queue to its acks' release), timed for
    # its CPU alone: settle.release_cpu_us, and only where the registry
    # has waits on. No histogram of its own, no annotation (it spans
    # settle.standby_wait and settle.persist).
    "settle.release",
})

_tls = threading.local()


def stage_open() -> bool:
    """Whether the calling thread has a stage annotation open, as far
    as laps of a waits-on registry have marked it."""
    return getattr(_tls, "open", False)


def _annotation_cls():
    """`jax.profiler.TraceAnnotation`, or None where JAX is absent.
    Resolved when a stage is created (cold path), never at import."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class Stage:
    """A named host stage: `hist` is its `<name>_us` histogram (None for
    an annotation-only stage whose interval an older histogram already
    times), `clock` the registry's clock. `cpu_clock` is the registry's
    CPU clock where it has waits on (None otherwise) and `cpu_hist` the
    stage's `<name>_cpu_us`, for the stages that asked for one."""

    __slots__ = ("name", "hist", "clock", "_ann_cls", "cpu_clock",
                 "cpu_hist")

    def __init__(self, name: str, hist, clock: Callable[[], float],
                 annotate: bool = True,
                 cpu_clock: Optional[Callable[[], float]] = None,
                 cpu_hist=None) -> None:
        self.name = name
        self.hist = hist
        self.clock = clock
        self._ann_cls = _annotation_cls() if annotate else None
        self.cpu_clock = cpu_clock
        self.cpu_hist = cpu_hist

    def annotate(self):
        """Open this stage's profiler annotation on the calling thread;
        the caller closes it with `__exit__`. None when not annotating."""
        if self._ann_cls is None:
            return None
        ann = self._ann_cls(self.name)
        ann.__enter__()
        return ann

    def timed(self) -> "StageLap":
        lap = StageLap(self.clock, self.cpu_clock)
        lap.to(self)
        return lap


class StageLap:
    """One thread's time partitioned into stages (see module doc)."""

    __slots__ = ("_clock", "_stage", "_t0", "_ann", "_cpu", "_c0")

    def __init__(self, clock: Callable[[], float],
                 cpu_clock: Optional[Callable[[], float]] = None) -> None:
        self._clock = clock
        self._stage: Optional[Stage] = None
        self._t0 = 0.0
        self._ann = None
        self._cpu = cpu_clock  # None: the registry has waits off
        self._c0 = 0.0

    def to(self, stage: Optional[Stage]) -> float:
        """Close the open stage and open `stage` (None: just close) on
        ONE clock read, which is returned."""
        t = self._clock()
        cur = self._stage
        if cur is not None:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            if cur.hist is not None:
                cur.hist.observe(t - self._t0)
        self._stage, self._t0 = stage, t
        self._ann = stage.annotate() if stage is not None else None
        if self._cpu is not None:
            self._waits(cur, stage)
        return t

    def _waits(self, cur: Optional[Stage], stage: Optional[Stage]) -> None:
        """The waits-on half of a boundary: the CPU clock, read once if
        either side of the boundary asked for it, and the thread's
        stage-open mark."""
        closing = cur is not None and cur.cpu_hist is not None
        if closing or (stage is not None and stage.cpu_hist is not None):
            c = self._cpu()
            if closing:
                cur.cpu_hist.observe(c - self._c0)
            self._c0 = c
        _tls.open = self._ann is not None

    def __enter__(self) -> "StageLap":
        return self

    def __exit__(self, *exc) -> None:
        self.to(None)


class _NullStage:
    """The disabled-registry twin: same surface, no clock, no objects."""

    __slots__ = ()
    name = ""
    hist = None
    cpu_hist = None

    def annotate(self):
        return None

    def timed(self) -> "_NullLap":
        return NULL_LAP


class _NullLap:
    __slots__ = ()

    def to(self, stage) -> float:
        return 0.0

    def __enter__(self) -> "_NullLap":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_LAP = _NullLap()
NULL_STAGE = _NullStage()
