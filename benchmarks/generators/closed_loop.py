"""Closed loop: clients that each wait for a reply before the next send.

Parameters:
  clients_per_process  producer clients (one thread and one ProducerClient
                       each) in every producer process
  batch                messages per produce call (1: the reference's own
                       sample-producer sends one message per RPC)
  rpc_timeout_s        a call's deadline

Client c of all clients writes to partition c mod (number of partitions),
so each partition has the same number of writers. Latency is timed from
the send. Each client stops sending at the window's end.
"""

from __future__ import annotations

import threading
import time

from benchmarks import payload


def run(ctx) -> dict:
    p = ctx.params
    per = int(p["clients_per_process"])
    batch = int(p.get("batch", 1))
    orders = ctx.orders
    sent_in_window = [0] * per

    def client(i: int) -> None:
        cid = ctx.proc_id * per + i
        s = cid % len(ctx.streams)
        topic, part = ctx.streams[s]
        pc = ctx.make_producer()
        seq = 0
        while not orders.gone:
            send = time.monotonic_ns()
            if orders.window.is_set() and send >= orders.t1:
                return
            msgs = payload.to_messages(ctx.block(s, cid, seq, batch, send))
            in_window = orders.window.is_set() and send >= orders.t0
            try:
                if batch == 1:
                    off = pc.produce(topic, msgs[0], partition=part)
                else:
                    off = pc.produce_batch(topic, msgs, partition=part)
            except Exception as e:
                ctx.failed(send, batch, e)
                time.sleep(0.05)
                seq += batch  # the range may be on the wire: never reuse it
                continue
            finally:
                if in_window:
                    sent_in_window[i] += batch
            ctx.acked(s, cid, seq, batch, send, off, send,
                      time.monotonic_ns())
            seq += batch

    threads = [threading.Thread(target=client, args=(i,)) for i in range(per)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"due_msgs": sum(sent_in_window),
            "due_calls": sum(sent_in_window) // batch, "late_ms": []}
