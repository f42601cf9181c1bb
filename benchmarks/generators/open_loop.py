"""Open loop: `produce_batch` calls at a fixed rate that does not slow when
the system slows; latency is timed from the moment a call was due.

Parameters (the cell's `generator.params`):
  rate_msgs_per_s   total offered rate over all producer processes
  batch             messages per produce call (one partition per call)
  senders           threads per process that carry the blocking calls
  rpc_timeout_s     a call's deadline
  rate_steps_msgs_per_s  (the builder's knee sweep only) a list of rates,
                    each offered for an equal share of the window, in
                    place of rate_msgs_per_s

Each producer process is a rate limiter, as the OpenMessaging Benchmark's
own producers are: its calls are due at even intervals. The seed sets each
process's phase within one interval and the order in which it walks its
partitions, so every seed offers the same number of calls of the same size
at the same spacing, to the partitions in another order. Before the window
the same pattern runs as warm-up.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from benchmarks import payload


def schedule(phase: float, t0: int, t1: int, rate: float):
    """Due times (ns) in [t0, t1): round(rate x length) of them, evenly
    spaced, the first `phase` (0..1) of an interval after t0."""
    n = int(round(rate * (t1 - t0) / 1e9))
    return (t0 + (np.arange(n) + phase) * ((t1 - t0) / max(1, n))).astype(
        np.int64)


def run(ctx) -> dict:
    p = ctx.params
    batch = int(p["batch"])
    first = (p.get("rate_steps_msgs_per_s") or [p["rate_msgs_per_s"]])[0]
    rate_calls = float(first) / batch / ctx.nprocs
    mine = list(range(ctx.proc_id, len(ctx.streams), ctx.nprocs))
    pc = ctx.make_producer()
    jobs: queue.Queue = queue.Queue()
    late_ms: list[float] = []
    seq = [0]
    seq_lock = threading.Lock()
    orders = ctx.orders

    def sender() -> None:
        while True:
            job = jobs.get()
            if job is None:
                return
            due, s = job
            with seq_lock:
                seq0 = seq[0]
                seq[0] += batch
            topic, part = ctx.streams[s]
            msgs = payload.to_messages(
                ctx.block(s, ctx.proc_id, seq0, batch, due))
            send = time.monotonic_ns()
            try:
                off = pc.produce_batch(topic, msgs, partition=part)
            except Exception as e:
                ctx.failed(due, batch, e)
                continue
            ack = time.monotonic_ns()
            ctx.acked(s, ctx.proc_id, seq0, batch, due, off, send, ack)
            if orders.window.is_set() and orders.t0 <= due < orders.t1:
                late_ms.append((send - due) / 1e6)

    threads = [threading.Thread(target=sender) for _ in range(int(p["senders"]))]
    for t in threads:
        t.start()
    order = ctx.rng(2).permutation(len(mine))
    phase = float(ctx.rng(0).random())
    rr = 0
    due_count = 0

    def issue(due: int) -> None:
        nonlocal rr
        now = time.monotonic_ns()
        if due > now:
            time.sleep((due - now) / 1e9)
        jobs.put((int(due), mine[order[rr % len(mine)]]))
        rr += 1

    # Warm-up: the same spacing, until the window is set and opens.
    nxt = time.monotonic_ns()
    while not orders.gone:
        if orders.window.is_set() and nxt >= orders.t0:
            break
        issue(nxt)
        nxt += int(1e9 / rate_calls)
    if not orders.gone:
        steps = p.get("rate_steps_msgs_per_s") or [p["rate_msgs_per_s"]]
        edges = np.linspace(orders.t0, orders.t1, len(steps) + 1).astype(np.int64)
        for k, rate in enumerate(steps):
            for due in schedule(phase, int(edges[k]), int(edges[k + 1]),
                                float(rate) / batch / ctx.nprocs):
                issue(due)
                due_count += 1
    for _ in threads:
        jobs.put(None)
    for t in threads:
        t.join()
    return {"due_calls": due_count, "due_msgs": due_count * batch,
            "late_ms": late_ms}
