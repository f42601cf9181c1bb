"""Wake-up probe: how long a runnable thread waits for the interpreter.

One daemon thread of a traced broker (`trace_sample_n > 0`) sleeps a
fixed `PERIOD_S` on an Event and observes `interp.wake_late_us` = the
time it ran again minus the time it asked to. A sleeping thread that
becomes runnable needs the interpreter exactly as an RPC worker woken
by its future, a resolver woken by the device or the step thread
returning from the launch does, so its lateness is the price of one
hand-over under the load of the moment (CPython hands over at most
every switch interval per holder). A lateness past `STALL_S` leaves one
`interp_stall` event in the flight recorder: a stall of seconds then
has a name in the ring the post-mortem dumps.

On the registry's clock, so tests drive `step()` on a fake one with no
thread and no sleep.
"""

from __future__ import annotations

import threading

PERIOD_S = 0.005
STALL_S = 0.05


class WakeProbe:
    def __init__(self, metrics, recorder, period_s: float = PERIOD_S,
                 wait=None) -> None:
        self._hist = metrics.histogram("interp.wake_late_us")
        self._clock = metrics.clock
        self._recorder = recorder
        self._period_s = period_s
        self._stop = threading.Event()
        # `wait(seconds) -> stopped`: the Event's own, so stop() ends a
        # sleep at once; tests hand in one that moves their clock.
        self._wait = wait if wait is not None else self._stop.wait
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="wake-probe")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=1.0)

    def alive(self) -> bool:
        return self._thread.is_alive()

    def step(self) -> bool:
        """One sleep and its observation; True once stopped."""
        due = self._clock() + self._period_s
        if self._wait(self._period_s):
            return True
        late = self._clock() - due
        self._hist.observe(late)
        if late > STALL_S:
            self._recorder.record("interp_stall",
                                  late_ms=round(late * 1e3, 3))
        return False

    def _run(self) -> None:
        while not self.step():
            pass
