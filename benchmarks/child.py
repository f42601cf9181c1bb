"""Role children of a benchmark run: producers, consumers, data-dir scanners.

Started by harness.py as `python -m benchmarks.child --role R --spec F` with
JAX_PLATFORMS=cpu from outside (none of them owns a chip). A child boots
while the cluster does, prints READY, and then takes its orders over stdin:

    PROBE                       one producer: send one batch with a deadline
                                long enough to outlast the broker's own
                                warm-up, and say FIRSTACK when it is acked
    GO                          start the cell's traffic (warm-up phase)
    WINDOW <t0_ns> <t1_ns>      the measured window, on time.monotonic_ns()
                                of this machine; traffic goes on unbroken
    DRAIN <deadline_ns> <file>  consumers: read on until every partition
                                holds the counts in <file> under every
                                subscription, at the latest until the
                                deadline (- = no counts: stop now)

and answers with one `RESULT <json>` line; bulk data goes to .npy files in
the run's work dir. Producers also print `FIRSTACK <ns>` once.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import threading
import time

import numpy as np

from benchmarks import payload
from benchmarks.reference_log import RECORD, ReferenceLog, compare_all


def log(*a) -> None:
    print(*a, flush=True)


class Orders:
    """The parent's lines, read on a thread so that traffic never blocks
    on stdin. An order is whole before its event is set: whoever sees
    `window` sees t0 and t1, whoever sees `drain` sees the deadline and
    the counts to wait for."""

    def __init__(self, stdin=None) -> None:
        self.go = threading.Event()
        self.probe = threading.Event()
        self.window = threading.Event()
        self.drain = threading.Event()
        self.t0 = self.t1 = 0
        self.drain_deadline = 0
        # Messages a stream must hold before its consumer may leave the
        # drain. None has one meaning: the cell's delivery is `prefix`
        # (the order came with `-`) and a consumer leaves at DRAIN.
        self.want = None
        self.drain_error: str | None = None
        self.gone = False
        self._stdin = sys.stdin if stdin is None else stdin
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self._stdin:
            w = line.split()
            if not w:
                continue
            if w[0] == "GO":
                self.go.set()
            elif w[0] == "PROBE":
                self.probe.set()
            elif w[0] == "WINDOW":
                self.t0, self.t1 = int(w[1]), int(w[2])
                self.window.set()
            elif w[0] == "DRAIN":
                self.drain_deadline = int(w[1])
                if w[2] != "-":
                    try:
                        self.want = np.load(w[2])
                    except Exception as e:  # counts that cannot be had
                        self.drain_error = (f"DRAIN counts {w[2]}: "
                                            f"{type(e).__name__}: {e}")
                self.drain.set()
        self.gone = True  # parent went away: let every wait end
        for e in (self.go, self.probe, self.window, self.drain):
            e.set()


class Ctx:
    """What a generator gets: the cell's parameters, this process's share
    of the partitions, the seeded pool, and where to put its records."""

    def __init__(self, spec: dict, orders: Orders) -> None:
        self.spec = spec
        self.params = spec["params"]
        self.orders = orders
        self.seed = int(spec["seed"])
        self.proc_id = int(spec["proc_id"])
        self.nprocs = int(spec["nprocs"])
        self.size = int(spec["message_bytes"])
        self.streams = [tuple(s) for s in spec["streams"]]
        self.pool = payload.make_pool(self.seed)
        self.rpc_timeout_s = float(self.params.get("rpc_timeout_s", 30.0))
        self._lock = threading.Lock()
        self._records: list[tuple] = []
        self.first_acked = False
        self.failed_calls: list[tuple] = []  # (stamp_ns, n, error)
        self.producers: list = []

    def rng(self, *key: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, self.proc_id, *key])

    def make_producer(self):
        from ripplemq_tpu.client import ProducerClient

        pc = ProducerClient(
            self.spec["bootstrap"], rpc_timeout_s=self.rpc_timeout_s,
            trace_sample_n=int(self.spec.get("trace_sample_n", 0)))
        self.producers.append(pc)
        return pc

    def block(self, stream: int, client: int, seq0: int, n: int,
              stamp: int) -> np.ndarray:
        return payload.build(
            self.pool, stream, client,
            np.arange(seq0, seq0 + n, dtype=np.uint32), stamp, self.size)

    def acked(self, stream, client, seq0, n, stamp, offset, send, ack) -> None:
        with self._lock:
            self._records.append(
                (stream, client, seq0, n, stamp, offset, send, ack))
            first = not self.first_acked
            self.first_acked = True
        if first:
            log(f"FIRSTACK {ack}")

    def failed(self, stamp: int, n: int, err: BaseException) -> None:
        with self._lock:
            self.failed_calls.append(
                (int(stamp), int(n), f"{type(err).__name__}: {err}"[:200]))

    def records(self) -> np.ndarray:
        with self._lock:
            return np.array(self._records, dtype=RECORD)


def probe(ctx: Ctx) -> None:
    """The first produce of a run. The broker builds its round programs
    with the device lock held, so the first ack marks the end of its
    warm-up; traffic offered before that only queues (and competes with
    the program build for the controller's interpreter)."""
    from ripplemq_tpu.client import ProducerClient

    pc = ProducerClient(ctx.spec["bootstrap"], rpc_timeout_s=600.0,
                        retries=5)
    try:
        s, cid = ctx.proc_id, 0xFFFF
        topic, part = ctx.streams[s]
        n = int(ctx.params.get("batch", 1))
        stamp = time.monotonic_ns()
        off = pc.produce_batch(
            topic, payload.to_messages(ctx.block(s, cid, 0, n, stamp)),
            partition=part)
        ctx.acked(s, cid, 0, n, stamp, off, stamp, time.monotonic_ns())
    finally:
        pc.close()


def role_produce(spec: dict, orders: Orders) -> dict:
    gen = importlib.import_module(
        f"benchmarks.generators.{spec['generator']}")
    ctx = Ctx(spec, orders)
    while not orders.go.wait(0.05):
        if orders.probe.is_set() and not ctx.first_acked:
            probe(ctx)
    extra = gen.run(ctx) or {}
    recs = ctx.records()
    np.save(os.path.join(spec["work"], f"records-{ctx.proc_id}.npy"), recs)
    spans = []
    for pc in ctx.producers:
        if pc.spans is not None:
            spans.extend(pc.spans.snapshot())
        pc.close()
    with open(os.path.join(spec["work"], f"spans-client-{ctx.proc_id}.json"),
              "w") as f:
        json.dump(spans, f)
    t0, t1 = orders.t0, orders.t1
    in_window = [(s, n, e) for s, n, e in ctx.failed_calls if t0 <= s < t1]
    return dict(extra, calls=len(recs),
                failed_calls_in_window=len(in_window),
                failed_msgs_in_window=sum(n for _, n, _ in in_window),
                failed_calls_total=len(ctx.failed_calls),
                errors=[e for _, _, e in ctx.failed_calls[:4]])


def hosted(n_subs: int, proc_id: int, nprocs: int) -> list[tuple[int, int, int]]:
    """What consumer process `proc_id` of `nprocs` hosts of a cell's
    `n_subs` subscriptions: (subscription index, part, of) - the process
    covers streams `part::of` of that subscription. Fewer subscriptions
    than processes: the processes share each subscription's streams (one
    subscription over four processes is `(0, proc_id, 4)`, as it always
    was). As many or more: a process hosts whole subscriptions."""
    m = min(n_subs, nprocs)
    out = []
    for q in range(n_subs):
        if q % m == proc_id % m:
            procs = [i for i in range(nprocs) if i % m == q % m]
            out.append((q, procs.index(proc_id), len(procs)))
    return out


def thread_share(entries: list, tid: int, threads: int,
                 n_streams: int) -> tuple[int, list[int]]:
    """Thread `tid` of a consumer process's `threads`: (position in
    `entries` of the hosted subscription it consumes under, its streams).
    The threads go round the hosted subscriptions, and those that share
    one share its streams, so every (subscription, stream) a process
    hosts has exactly one thread - given at least a thread a hosted
    subscription, which `run.child_spec` sees to."""
    e = tid % len(entries)
    mates = range(e, threads, len(entries))
    _, part, of = entries[e]
    return e, list(range(part, n_streams, of))[tid // len(entries)::len(mates)]


def client_kwargs(spec: dict) -> dict:
    """What every `ConsumerClient` of this process is built with beside
    the bootstrap and its subscription: the harness's three from `params`
    and the cell's `consumers.client`. Bound to the client's signature, so
    a key it does not take, or one the harness sets, is a TypeError - from
    `main` before READY, which ends the run with no result."""
    import inspect

    from ripplemq_tpu.client import ConsumerClient

    p = spec["params"]
    kw = dict(max_messages=int(p["max_messages"]),
              prefetch=int(p.get("prefetch", 0)),
              rpc_timeout_s=float(p.get("rpc_timeout_s", 30.0)))
    inspect.signature(ConsumerClient).bind(
        spec["bootstrap"], "", **kw, **spec.get("client", {}))
    return {**kw, **spec.get("client", {})}


def role_consume(spec: dict, orders: Orders) -> dict:
    from ripplemq_tpu.client import ConsumerClient

    p = spec["params"]
    client_kw = client_kwargs(spec)
    size = int(spec["message_bytes"])
    threads = int(p["threads"])
    streams = [tuple(s) for s in spec["streams"]]
    # [name, subscription index, part, of], as run.child_spec hands them
    subs = spec["subscriptions"]
    entries = [(int(q), int(part), int(of)) for _, q, part, of in subs]
    poll_s = float(p.get("poll_interval_s", 0.0))
    idle_s = float(p.get("idle_sleep_s", 0.002))
    # (hosted subscription, stream, recv_ns, blob, n) per thread
    chunks: list[list] = [[] for _ in range(threads)]
    counts = np.zeros((len(subs), len(streams)), np.int64)
    errors: list[str] = []
    short_at_deadline = [0] * threads
    fault = [spec.get("fault")]
    orders.go.wait()

    def run(tid: int) -> None:
        e, own = thread_share(entries, tid, threads, len(streams))
        if not own:
            return
        cc = ConsumerClient(spec["bootstrap"], subs[e][0], **client_kw)
        have = counts[e]
        gap = poll_s / len(own)
        nxt = time.monotonic() + gap * (tid / max(1, threads))
        i = 0
        empty_run = 0
        try:
            while not orders.gone:
                if orders.drain.is_set():
                    want = orders.want
                    if want is None:  # delivery `prefix`: leave at DRAIN
                        break
                    short = [s for s in own if have[s] < want[s]]
                    if not short:
                        break
                    if time.monotonic_ns() >= orders.drain_deadline:
                        short_at_deadline[tid] = len(short)
                        break
                    s = short[i % len(short)]
                elif gap > 0:
                    now = time.monotonic()
                    if nxt > now:
                        time.sleep(nxt - now)
                    nxt = max(nxt + gap, time.monotonic() - 5 * gap)
                    s = own[i % len(own)]
                else:
                    s = own[i % len(own)]
                i += 1
                topic, part = streams[s]
                msgs = cc.consume(topic, partition=part)
                if msgs and fault[0] and orders.window.is_set() \
                        and time.monotonic_ns() > orders.t0:
                    # A control, never a benchmark run: the answer is
                    # altered where it is produced.
                    if fault[0] == "flip_delivered":
                        m = bytearray(msgs[0])
                        m[len(m) // 2] ^= 0x01
                        msgs[0] = bytes(m)
                    elif fault[0] == "drop_delivered":
                        del msgs[0]
                    fault[0] = None
                if msgs:
                    chunks[tid].append(
                        (e, s, time.monotonic_ns(), b"".join(msgs), len(msgs)))
                    have[s] += len(msgs)
                    empty_run = 0
                else:
                    empty_run += 1
                    if gap == 0 and empty_run >= len(own):
                        time.sleep(idle_s)
                        empty_run = 0
                    elif orders.drain.is_set():
                        time.sleep(idle_s)
        except Exception as err:  # a dead consumer fails the run
            errors.append(
                f"consumer thread {tid}: {type(err).__name__}: {err}")
        finally:
            cc.close()

    ts = [threading.Thread(target=run, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if orders.drain_error:
        errors.append(orders.drain_error)

    t0, t1 = orders.t0, orders.t1
    held: dict[tuple[int, int], list[bytes]] = {}  # (subscription, stream)
    lats, lat_stamps, lat_subs = [], [], []
    got_by_t1 = [0] * len(subs)
    ragged = 0
    for tchunks in chunks:
        for e, s, recv, blob, n in tchunks:
            held.setdefault((entries[e][0], s), []).append(blob)
            if len(blob) != n * size:
                ragged += 1
                continue
            rows = np.frombuffer(blob, np.uint8).reshape(n, size)
            st = payload.stamps_of(rows).astype(np.int64)
            m = (st >= t0) & (st < t1)
            if m.any():
                lats.append((recv - st[m]) / 1e6)
                lat_stamps.append(st[m])
                lat_subs.append(np.full(int(m.sum()), entries[e][0], np.int16))
            if recv <= t1:
                got_by_t1[e] += n
    chunks.clear()
    order = sorted(held)
    blobs = [b"".join(held.pop(k)) for k in order]
    pid = int(spec["proc_id"])
    out = os.path.join(spec["work"], f"recv-{pid}.")
    np.save(out + "index.npy",
            np.array([(q, s, len(b)) for (q, s), b in zip(order, blobs)],
                     np.int64).reshape(-1, 3))
    np.save(out + "bytes.npy", np.frombuffer(b"".join(blobs), np.uint8))
    for name, parts, dtype in (("lat", lats, np.float64),
                               ("latstamp", lat_stamps, np.int64),
                               ("latsub", lat_subs, np.int16)):
        np.save(out + name + ".npy",
                np.concatenate(parts) if parts else np.zeros(0, dtype))
    return {"received": int(counts.sum()),
            "received_by_t1": {name: n for (name, *_), n
                               in zip(subs, got_by_t1)},
            "ragged_chunks": ragged, "errors": errors[:4],
            "short_at_deadline": sum(short_at_deadline)}


def load_records(work: str) -> np.ndarray:
    parts = [np.load(os.path.join(work, f)) for f in sorted(os.listdir(work))
             if f.startswith("records-") and f.endswith(".npy")]
    return np.concatenate(parts) if parts else np.zeros(0, RECORD)


def scan_dir(store_dir: str, slot_bytes: int, size: int) -> dict:
    """One data dir, read the way recovery reads it (the program's own
    `scan_store`): stream -> the bytes of its messages, in log order."""
    from ripplemq_tpu.storage.segment import REC_APPEND, scan_store

    rows: dict[int, dict[int, bytes]] = {}
    for rec_type, slot, base, body in scan_store(store_dir):
        if rec_type == REC_APPEND:
            rows.setdefault(slot, {})[base] = body
    got: dict[int, bytes] = {}
    w = slot_bytes - 8
    for slot, recs in rows.items():
        parts = []
        for base in sorted(recs):
            block = np.frombuffer(recs[base], np.uint8).reshape(-1, slot_bytes)
            lens = block[:, :4].copy().view("<i4")[:, 0]
            keep = block[lens > 0]
            if (lens[lens > 0] != size).any() or size > w:
                # a row of another length: keep it visible as a difference
                parts.append(b"\xff" * size)
                continue
            parts.append(np.ascontiguousarray(keep[:, 8:8 + size]).tobytes())
        blob = b"".join(parts)
        if not blob:
            continue
        stream = int(np.frombuffer(blob[:2], "<u2")[0])
        got[stream] = got.get(stream, b"") + blob  # two slots, one stream: shows
    return got


def role_scan(spec: dict, orders: Orders) -> dict:
    orders.go.wait()
    ref = ReferenceLog(spec["seed"], spec["message_bytes"],
                       load_records(spec["work"]), len(spec["streams"]))
    got = scan_dir(spec["store_dir"], int(spec["slot_bytes"]),
                   int(spec["message_bytes"]))
    res = compare_all(ref, got, prefix_ok=False)
    res.update(messages=sum(len(b) for b in got.values())
               // int(spec["message_bytes"]), acked=ref.total)
    return res


ROLES = {"produce": role_produce, "consume": role_consume, "scan": role_scan}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", required=True, choices=sorted(ROLES))
    ap.add_argument("--spec", required=True)
    args = ap.parse_args()
    with open(args.spec) as f:
        spec = json.load(f)
    if args.role != "scan":  # imports off the clock, before READY
        import ripplemq_tpu.client  # noqa: F401
    if args.role == "consume":
        client_kwargs(spec)
    orders = Orders()
    log("READY")
    log("RESULT " + json.dumps(ROLES[args.role](spec, orders)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
