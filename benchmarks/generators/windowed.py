"""Windowed senders: offered load above what the system serves.

The pattern of the program's own `samples/loadgen.py`, with seeded distinct
payloads: every thread keeps `window` `produce_batch_async` calls of
`batch` messages outstanding and lands them first-in first-out, so the
load offered is whatever the system will take. The queue grows all run;
the ack times taken here are when the thread GOT to each reply, and judge
nothing.

Parameters: threads, window, batch, rpc_timeout_s. A thread's partitions
are its own (no two calls of one partition are in flight from two threads).
"""

from __future__ import annotations

import threading
import time
from collections import deque

from benchmarks import payload


def run(ctx) -> dict:
    p = ctx.params
    nthreads, window, batch = int(p["threads"]), int(p["window"]), int(p["batch"])
    mine = list(range(ctx.proc_id, len(ctx.streams), ctx.nprocs))
    pc = ctx.make_producer()
    orders = ctx.orders
    sent_in_window = [0] * nthreads

    def worker(tid: int) -> None:
        own = mine[tid::nthreads]
        if not own:
            return
        cid = ctx.proc_id * nthreads + tid
        start = int(ctx.rng(3, tid).integers(0, len(own)))
        pending: deque = deque()
        seq = 0
        i = 0

        def land() -> None:
            waiter, s, seq0, send = pending.popleft()
            try:
                off = waiter()
            except Exception as e:
                ctx.failed(send, batch, e)
                return
            ctx.acked(s, cid, seq0, batch, send, off, send,
                      time.monotonic_ns())

        while not orders.gone:
            now = time.monotonic_ns()
            if orders.window.is_set() and now >= orders.t1:
                break
            while len(pending) >= window:
                land()
            s = own[(start + i) % len(own)]
            i += 1
            topic, part = ctx.streams[s]
            send = time.monotonic_ns()
            msgs = payload.to_messages(ctx.block(s, cid, seq, batch, send))
            try:
                w = pc.produce_batch_async(topic, msgs, partition=part)
            except Exception as e:
                ctx.failed(send, batch, e)
                time.sleep(0.05)
                seq += batch
                continue
            if orders.window.is_set() and orders.t0 <= send < orders.t1:
                sent_in_window[tid] += batch
            pending.append((w, s, seq, send))
            seq += batch
        while pending:
            land()

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"due_msgs": sum(sent_in_window),
            "due_calls": sum(sent_in_window) // batch, "late_ms": []}
