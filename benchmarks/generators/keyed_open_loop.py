"""Keyed open loop: single messages, each with its own key, through the
program's batching producer (`ProducerClient.send`) at a fixed rate that
does not slow when the system slows.

What a user who keys records by entity sends: every message is due at its
own moment (even spacing, seeded phase, as `open_loop`), its key is drawn
from the configuration's popularity law (`deployment.keys`: YCSB's
zipfian over `count` keys, the rank-to-key mapping a seeded permutation
re-drawn every `rotation_s` seconds, so the hot set moves), the
program's own key-hash ranges pick the partition, and the program's own
accumulator (`deployment.producer`: linger, in-flight window) decides
what rides which request. Nothing here batches.

Parameters (the cell's `generator.params`):
  config            the configuration file to read `deployment.keys` and
                    `deployment.producer` from
  rate_msgs_per_s   total offered rate over all producer processes
  arrival           "even": due times evenly spaced (the one law there is;
                    a bursty one is a branch of `schedule` and a traffic
                    file, when a cell asks for it)
  tick_s            how often the dispatcher looks for due messages
  rpc_timeout_s     a request's deadline
  rate_steps_msgs_per_s  (the builder's knee sweep only) as `open_loop`

Records. One line per acked PART (the partition batch a produce.multi
request carried): `stream` is the partition the ack named, `seq0`..`n`
the producer's own per-partition message counter, `offset` the part's
base offset, `stamp` the due time of the part's OLDEST message - which
every message of the part carries in its bytes, because the reference
rebuilds a record's messages from one stamp. The dispatcher knows a
message will open a new part when the previous message it sent to that
partition has left the accumulator (`SendWaiter.sent()`); otherwise it
reuses the open part's stamp. Ack and delivery latency are therefore
timed from the due time of a part's oldest message, linger included.

Per-key order is checked here, at the ack: within one partition this
producer's parts must be acked at offsets that rise with send order, a
part's messages must sit at consecutive offsets in send order, and an
ack must name the partition the key routed to. Anything else is a failed
call, which makes the run not `correct`.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque

import numpy as np

from benchmarks import payload
from ripplemq_tpu.client import ProducerClient

if not hasattr(ProducerClient, "send"):
    raise ImportError("this program's ProducerClient has no send(): the "
                      "keyed deployment cannot run on it")

HERE = os.path.dirname(os.path.abspath(__file__))


class KeySpace:
    """The configuration's key popularity: a pure function of the seed.
    `ranks` draws popularity ranks (0 = hottest) by inverse CDF;
    `key_ids` maps them through the epoch's permutation of the key ids,
    rank -> (a x rank + b) mod count with a coprime to count: a
    bijection drawn anew for every epoch, with no table to build when
    the epoch turns (the keys are hashed before they meet a partition,
    so its regularity shows nowhere)."""

    def __init__(self, seed: int, count: int, theta: float) -> None:
        self.seed, self.count = int(seed), int(count)
        w = np.arange(1, self.count + 1, dtype=np.float64) ** -float(theta)
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]
        self._perms: dict[int, tuple[int, int]] = {}

    def ranks(self, rng: np.random.Generator, n: int) -> np.ndarray:
        return np.minimum(np.searchsorted(self.cdf, rng.random(n),
                                          side="right"), self.count - 1)

    def perm(self, epoch: int) -> tuple[int, int]:
        ab = self._perms.get(epoch)
        if ab is None:
            rng = np.random.default_rng(
                [self.seed, 0x6B657973, epoch & 0xFFFFFFFF])
            a = 0
            while math.gcd(a, self.count) != 1:
                a = int(rng.integers(1, self.count))
            ab = self._perms[epoch] = (a, int(rng.integers(0, self.count)))
        return ab

    def key_ids(self, epoch: int, ranks: np.ndarray) -> np.ndarray:
        a, b = self.perm(epoch)
        return (ranks.astype(np.int64) * a + b) % self.count


def key_bytes(key_id: int) -> bytes:
    return b"key-%07d" % key_id


def schedule(phase: float, t0: int, t1: int, rate: float,
             arrival: str) -> np.ndarray:
    """Due times (ns) in [t0, t1): round(rate x length) of them, evenly
    spaced from `phase` (0..1) of an interval after t0."""
    if arrival != "even":
        raise ValueError(f"unknown arrival {arrival!r}")
    n = int(round(rate * (t1 - t0) / 1e9))
    t = (np.arange(n) + phase) * ((t1 - t0) / max(1, n))
    return (t0 + t).astype(np.int64)


def run(ctx) -> dict:
    p = ctx.params
    with open(os.path.join(HERE, "..", "configs", f"{p['config']}.json")) as f:
        dep = json.load(f)["deployment"]
    keys_cfg = dict(dep["keys"], **p.get("keys", {}))  # rehearsal sizes
    prod_cfg = dep["producer"]
    space = KeySpace(ctx.seed, keys_cfg["count"], keys_cfg["zipfian_constant"])
    rotation_ns = int(float(keys_cfg["rotation_s"]) * 1e9)
    arrival = p.get("arrival", "even")
    tick_ns = int(float(p.get("tick_s", 0.001)) * 1e9)
    orders = ctx.orders
    stream_of = {tp: i for i, tp in enumerate(ctx.streams)}
    topic = ctx.streams[0][0]

    pc = ProducerClient(
        ctx.spec["bootstrap"], rpc_timeout_s=ctx.rpc_timeout_s,
        trace_sample_n=int(ctx.spec.get("trace_sample_n", 0)),
        idempotence=bool(prod_cfg["idempotent"]),
        linger_s=float(prod_cfg["linger_s"]),
        batch_size=int(prod_cfg["batch_size"]),
        max_in_flight=int(prod_cfg["max_in_flight"]))
    ctx.producers.append(pc)  # child.py collects its spans and closes it

    rng = ctx.rng(1)
    next_seq: dict[int, int] = {}
    last_waiter: dict[int, object] = {}
    open_stamp: dict[int, int] = {}
    pend: deque = deque()   # (waiter, stream, seq, stamp, send_ns)
    sent_all = threading.Event()
    late_ms: list[float] = []
    # stream -> base offset -> [seq0, n, stamp, send_ns, ack_ns]
    parts: dict[int, dict[int, list]] = {}
    wait_s = ctx.rpc_timeout_s * 4

    def emit(dues: np.ndarray, t_ref: int) -> None:
        """Send the messages due at `dues` (all due by now)."""
        n = len(dues)
        epochs = (dues - t_ref) // rotation_ns
        ranks = space.ranks(rng, n)
        kids = np.empty(n, np.int64)
        for e in np.unique(epochs):
            m = epochs == e
            kids[m] = space.key_ids(int(e), ranks[m])
        keys = [key_bytes(int(k)) for k in kids]
        streams = np.empty(n, np.int64)
        seqs = np.empty(n, np.int64)
        stamps = np.empty(n, np.int64)
        fresh: dict[int, int] = {}  # parts this tick opened
        for i, key in enumerate(keys):
            part = pc.partition_for(topic, key)
            if part is None:
                raise RuntimeError(f"no route for topic {topic!r}")
            s = stream_of[(topic, part)]
            streams[i] = s
            seqs[i] = next_seq.get(s, 0)
            next_seq[s] = int(seqs[i]) + 1
            if s not in fresh:
                lw = last_waiter.get(s)
                fresh[s] = open_stamp[s] if lw is not None \
                    and not lw.sent() else int(dues[i])
                open_stamp[s] = fresh[s]
            stamps[i] = fresh[s]
        block = payload.build(ctx.pool, streams, ctx.proc_id, seqs, stamps,
                              ctx.size)
        msgs = payload.to_messages(block)
        send = time.monotonic_ns()
        for i, key in enumerate(keys):
            s = int(streams[i])
            try:
                w = pc.send(topic, msgs[i], key)
            except Exception as e:
                ctx.failed(int(stamps[i]), 1, e)
                continue
            last_waiter[s] = w
            pend.append((w, s, int(seqs[i]), int(stamps[i]), send))
        if orders.window.is_set() and orders.t0 <= dues[0] < orders.t1:
            late_ms.append((send - int(dues[0])) / 1e6)  # the tick's oldest

    def reap() -> None:
        """Every waiter in send order; acked messages folded into their
        parts, with the order checks of the module docstring."""
        while True:
            if not pend:
                if sent_all.is_set():
                    return
                time.sleep(0.002)
                continue
            w, s, seq, stamp, send = pend.popleft()
            try:
                w(wait_s)
            except Exception as e:
                ctx.failed(stamp, 1, e)
                continue
            rec = parts.setdefault(s, {}).get(w.base_offset)
            if rec is None and w.index == 0:
                rec = parts[s][w.base_offset] = [seq, 0, stamp, send,
                                                 w.acked_ns]
            if (rec is None or w.partition != ctx.streams[s][1]
                    or seq != rec[0] + w.index or w.index != rec[1]
                    or stamp != rec[2]):
                ctx.failed(stamp, 1, RuntimeError(
                    f"order: stream {s} seq {seq} acked as message "
                    f"{w.index} of the part at {w.partition}/"
                    f"{w.base_offset}, which holds {rec}"))
                continue
            rec[1] += 1

    reaper = threading.Thread(target=reap)
    reaper.start()
    phase = float(ctx.rng(0).random())
    first = (p.get("rate_steps_msgs_per_s") or [p["rate_msgs_per_s"]])[0]

    def pace(dues: np.ndarray, t_ref_of) -> None:
        """Walk one array of due times, a tick's worth at a go."""
        i = 0
        while i < len(dues) and not orders.gone:
            now = time.monotonic_ns()
            if dues[i] > now:
                time.sleep(max(int(dues[i]) - now, tick_ns) / 1e9)
                now = time.monotonic_ns()
            j = int(np.searchsorted(dues, now, side="right"))
            j = max(j, i + 1)
            emit(dues[i:j], t_ref_of())
            i = j

    # Warm-up: the cell's own traffic at the first rate, a second at a
    # go, until the window is known and opens. Before the window is
    # known the rotation counts from the first message.
    t_start = time.monotonic_ns()
    ref = lambda: orders.t0 if orders.window.is_set() else t_start  # noqa: E731
    nxt = t_start
    while not orders.gone:
        end = nxt + int(1e9)
        if orders.window.is_set():
            if nxt >= orders.t0:
                break
            end = min(end, orders.t0)
        pace(schedule(phase, nxt, end, float(first) / ctx.nprocs, arrival),
             ref)
        nxt = end
    if not orders.gone:
        steps = p.get("rate_steps_msgs_per_s") or [p["rate_msgs_per_s"]]
        edges = np.linspace(orders.t0, orders.t1, len(steps) + 1).astype(
            np.int64)
        for k, rate in enumerate(steps):
            pace(schedule(phase, int(edges[k]), int(edges[k + 1]),
                          float(rate) / ctx.nprocs, arrival), ref)
    pc.flush(wait_s)
    sent_all.set()
    reaper.join()

    # One record per acked part; a partition's parts in send order must
    # have been acked at rising offsets.
    due_msgs = 0
    for s, by_base in parts.items():
        recs = sorted((rec[0], base, rec) for base, rec in by_base.items())
        top = -1
        for seq0, base, (_, n, stamp, send, ack) in recs:
            if base <= top:
                ctx.failed(stamp, n, RuntimeError(
                    f"order: stream {s} part seq0 {seq0} acked at {base}, "
                    f"not above an earlier part's {top}"))
                continue
            top = base + n - 1
            ctx.acked(s, ctx.proc_id, seq0, n, stamp, base, send, ack)
            if orders.t0 <= stamp < orders.t1:
                due_msgs += n
    due_msgs += sum(n for st, n, _ in ctx.failed_calls
                    if orders.t0 <= st < orders.t1)
    return {"due_calls": due_msgs, "due_msgs": due_msgs, "late_ms": late_ms}
