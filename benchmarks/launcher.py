"""Broker 0's launcher: the one process of a run that owns the chip.

Calls the program's own entry, `ripplemq_tpu.broker.__main__.main(argv)`,
in-process and on the main thread (it installs signal handlers), so that
two things the program cannot do today happen beside it, with no file of
the program changed:

  * every JAX compilation is written to <work>/compiles.jsonl with the
    time.monotonic_ns() it was logged at, so the harness can count what
    compiled inside the measured window (JAX's own compile logging);
  * in a traced run, when <work>/profile.start appears, a thread opens a
    `jax.profiler` window of the stated length - only the chip-owning
    process can trace the chip - reduces the trace with trace_reduce.py
    and writes <work>/trace_summary.json.

Usage: python -m benchmarks.launcher <work> <profile_seconds> -- <broker argv>
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time


class _CompileLog(logging.Handler):
    def __init__(self, path: str) -> None:
        super().__init__(level=logging.WARNING)
        self._f = open(path, "a", buffering=1)

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:
            return
        if msg.startswith(("Compiling ", "Finished tracing",
                           "Finished jaxpr to MLIR", "Finished XLA compil",
                           "Persistent compilation cache hit",
                           "Not writing persistent cache")):
            self._f.write(json.dumps(
                {"t_ns": time.monotonic_ns(), "msg": msg[:160]}) + "\n")


def _profile_when_asked(work: str, seconds: float) -> None:
    start_flag = os.path.join(work, "profile.start")
    while not os.path.exists(start_flag):
        time.sleep(0.05)
    import jax

    from benchmarks import trace_reduce

    out = os.path.join(work, "profile")
    summary: dict = {}
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        t_a = time.monotonic_ns()
        jax.profiler.start_trace(out, profiler_options=opts)
        t_b = time.monotonic_ns()
        time.sleep(seconds)
        t_c = time.monotonic_ns()
        jax.profiler.stop_trace()
        t_d = time.monotonic_ns()
        planes = trace_reduce.extract(trace_reduce.find_xplane(out))
        summary = trace_reduce.summarize(planes, window_s=(t_c - t_b) / 1e9)
        summary.update(start_ns=t_b, stop_ns=t_c,
                       start_cost_s=(t_b - t_a) / 1e9,
                       stop_cost_s=(t_d - t_c) / 1e9)
    except Exception as e:  # the harness fails the traced run on this
        summary = {"error": f"{type(e).__name__}: {e}"}
    tmp = os.path.join(work, "trace_summary.json.tmp")
    with open(tmp, "w") as f:
        json.dump(summary, f)
    os.replace(tmp, os.path.join(work, "trace_summary.json"))


def main() -> int:
    work, seconds = sys.argv[1], float(sys.argv[2])
    argv = sys.argv[sys.argv.index("--") + 1:]
    import jax

    jax.config.update("jax_log_compiles", True)
    handler = _CompileLog(os.path.join(work, "compiles.jsonl"))
    jax_log = logging.getLogger("jax")
    jax_log.addHandler(handler)  # log_compiles speaks at WARNING
    if seconds > 0:
        threading.Thread(target=_profile_when_asked, args=(work, seconds),
                         daemon=True).start()
    from ripplemq_tpu.broker.__main__ import main as broker_main

    return broker_main(argv)


if __name__ == "__main__":
    sys.exit(main())
