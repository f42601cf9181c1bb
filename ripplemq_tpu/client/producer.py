"""ProducerClient: produce(topic, message) against the broker cluster.

API parity with the reference's ProducerClient/Impl (reference:
mq-common/src/main/java/client/ProducerClientImpl.java:57-99): cached
metadata, round-robin partition selection, leader-directed send, close().
Upgrades: real batching (`produce_batch`), not-leader hint following,
honest address resolution (see package docstring), and IDEMPOTENT
produce (`idempotence=True`, the default): the client registers a
metadata-issued producer id once, stamps every batch with an ack-gated
per-partition sequence, and the broker's dedup table collapses replays —
a retried batch whose first attempt actually committed is acked with its
original offset instead of appending twice, including across controller
failover. The sequence only ADVANCES on an acked outcome, so every
retry of an unacked batch replays the same identity; a batch abandoned
after its sequence was put on the wire burns its range (the broker may
hold a settled entry for it — reusing the numbers for fresh payloads
would dedupe them away).

KEYED, BATCHING produce (`send(topic, message, key)`): what a Kafka
producer does. The key picks the partition by key-hash range; messages
accumulate per partition (client/accumulator.py); one sender thread
flushes, to each leader, ONE `produce.multi` request carrying every
partition batch ("part") that is ready — `linger_s` old or full — with
at most `max_in_flight` requests out per leader and at most ONE part in
flight per partition. Each part is acked or refused on its own; a
refused part is retried alone under its reserved (pid, seq), so a key's
messages commit once and in send order. See README "Client SDK".
"""

from __future__ import annotations

import itertools
import threading
import zlib

from ripplemq_tpu.obs.lockwitness import make_condition, make_lock
import time
import uuid
from typing import Optional

from ripplemq_tpu.client.accumulator import Accumulator, Part
from ripplemq_tpu.client.metadata import MetadataError, MetadataManager
from ripplemq_tpu.obs.spans import (
    NULL_SPAN,
    SpanRing,
    TraceContext,
    derive_trace_id,
    sampled,
)
from ripplemq_tpu.metadata.models import RANGE_SPACE
from ripplemq_tpu.client.selector import PartitionSelector, RoundRobinSelector
from ripplemq_tpu.wire.retry import RetryPolicy, fatal_response_error
from ripplemq_tpu.wire.transport import (
    RpcError,
    RpcTimeout,
    TcpClient,
    Transport,
)


class ProduceError(Exception):
    pass


def key_hash(key: bytes) -> int:
    """Deterministic key→range-space hash (crc32 mod RANGE_SPACE):
    stable across processes and runs, so the chaos checker can replay
    a keyed workload's routing decisions exactly."""
    return zlib.crc32(bytes(key)) % RANGE_SPACE


class SendWaiter:
    """What `send()` returns. Calling it waits for the message's part to
    be acked and returns the message's offset (ProduceError if the part
    failed for good). `partition`, `base_offset`, `index` and `acked_ns`
    describe the acked part: the partition the ack named, the part's
    first offset, this message's place in it, `time.monotonic_ns()` at
    the ack."""

    __slots__ = ("_part", "_index", "_cond")

    def __init__(self, part: Part, index: int, cond) -> None:
        self._part, self._index, self._cond = part, index, cond

    def _where(self) -> tuple[Part, int]:
        part, idx = self._part, self._index
        while part.moved is not None:  # rerouted: follow the message
            part, idx = part.moved[idx]
        self._part, self._index = part, idx
        return part, idx

    def done(self) -> bool:
        return self._where()[0].done

    def sent(self) -> bool:
        """The message's part has left the accumulator at least once: a
        message sent to that partition from now on rides a LATER part."""
        return self._where()[0].sent

    def __call__(self, timeout: Optional[float] = None) -> int:
        end = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                part, idx = self._where()
                if part.done:
                    break
                left = None if end is None else end - time.monotonic()
                if left is not None and left <= 0:
                    raise ProduceError("send not acked within the timeout")
                self._cond.wait(left)
        if part.error is not None:
            raise ProduceError(part.error)
        return part.base_offset + idx

    @property
    def partition(self) -> int:
        return self._where()[0].partition

    @property
    def base_offset(self) -> Optional[int]:
        return self._where()[0].base_offset

    @property
    def index(self) -> int:
        return self._where()[1]

    @property
    def acked_ns(self) -> int:
        return self._where()[0].acked_ns


class _Request:
    """One produce.multi in flight."""

    __slots__ = ("addr", "parts", "deadline", "fut", "finished", "rpc",
                 "tctx")

    def __init__(self, addr: str, parts: list, deadline: float) -> None:
        self.addr, self.parts, self.deadline = addr, parts, deadline
        self.fut = None
        self.finished = False
        self.rpc = NULL_SPAN   # client.rpc of the one context it carries
        self.tctx = None       # ... and that context's client.produce


class ProducerClient:
    def __init__(
        self,
        bootstrap: list[str],
        transport: Optional[Transport] = None,
        selector: Optional[PartitionSelector] = None,
        metadata_refresh_s: float = 10.0,
        rpc_timeout_s: float = 5.0,
        retries: int = 3,
        retry_backoff_s: float = 0.2,
        deadline_s: Optional[float] = None,
        retry_policy: Optional[RetryPolicy] = None,
        idempotence: bool = True,
        producer_name: Optional[str] = None,
        pid_refresh_s: float = 60.0,
        trace_sample_n: int = 0,
        linger_s: float = 0.001,
        batch_size: int = 1048576,
        max_in_flight: int = 5,
    ) -> None:
        self._transport = transport if transport is not None else TcpClient()
        self._owns_transport = transport is None
        # Idempotent-producer identity (see module docstring). The pid
        # registers LAZILY on the first produce that can reach a broker;
        # until then batches flow unstamped (at-least-once — the broker
        # still stamps the forwarded hop with its own pid). The name
        # embeds a per-instance nonce: a restarted producer's sequence
        # counters start at zero, so it must not inherit an old pid.
        self._idempotence = bool(idempotence)
        self._pid: Optional[int] = None
        self._pid_name = producer_name or f"producer/{uuid.uuid4().hex}"
        # Partition the LAST acked produce_batch landed in (the broker
        # names `routed_partition` when it forwarded a migrating-range
        # write during a split handoff; otherwise the pinned choice).
        # Chaos/bench callers read this to attribute each ack to the
        # right final log. Single-threaded-per-producer contract, like
        # the sequence counters.
        self.last_partition: Optional[int] = None
        # Session refresh: re-register (idempotent; the apply bumps the
        # replicated seen counter) at this cadence so the metadata
        # leader's pid reaper sees a live session. Keep it well under
        # the server's pid_retention_s (default 600 s); 0 disables.
        self._pid_refresh_s = float(pid_refresh_s)
        self._pid_registered_t = 0.0
        self._seq_lock = make_lock("ProducerClient._seq_lock")
        self._seqs: dict[tuple[str, int], int] = {}
        # Causal tracing (obs/spans.py): every trace_sample_n-th call
        # (deterministic on the producer name + a per-call counter)
        # opens a client.produce ROOT span whose context rides the
        # request's optional `tctx` field; 0 disables — no ring, no
        # counter tick, no clock read on the produce path. `spans` is
        # public: the assembler reads the client's half of each trace
        # here (admin.spans only covers server-side rings).
        self._trace_sample_n = int(trace_sample_n)
        self._trace_counter = itertools.count()
        self.spans: Optional[SpanRing] = (
            SpanRing(self._pid_name) if self._trace_sample_n > 0 else None
        )
        self._selector = selector or RoundRobinSelector()
        self._timeout = rpc_timeout_s
        # One retry discipline for every operation (wire/retry.py):
        # jittered exponential backoff under an optional per-call
        # deadline budget. `retries`/`retry_backoff_s` stay as the
        # simple knobs; pass `retry_policy` to control everything.
        self._retry = retry_policy or RetryPolicy(
            max_attempts=retries,
            base_backoff_s=retry_backoff_s,
            deadline_s=deadline_s,
        )
        self._meta = MetadataManager(
            self._transport,
            bootstrap,
            refresh_interval_s=metadata_refresh_s,
            rpc_timeout_s=rpc_timeout_s,
        )
        self._meta.start()
        # The keyed path (`send`): Kafka's three knobs under Kafka's
        # names — linger.ms (as seconds), batch.size (bytes; a part is
        # also full at the engine's max_batch rows, which the brokers
        # advertise), max.in.flight.requests.per.connection. The sender
        # thread starts with the first send().
        self._acc = Accumulator(linger_s, batch_size, max_in_flight,
                                max_rows=lambda: self._meta.max_batch)
        self._acc_cond = make_condition("ProducerClient._acc_cond")
        self._done_cond = make_condition("ProducerClient._done_cond")
        self._sender: Optional[threading.Thread] = None
        # When the sender will next look on its own: None = only when
        # notified, 0.0 = it is looking now.
        self._sender_wake: Optional[float] = 0.0
        self._requests: list[_Request] = []
        self._need_refresh = False
        self._next_lookup = 0.0
        self._closing = False

    # ------------------------------------------------------------------ API

    def produce(self, topic: str, message: bytes,
                partition: Optional[int] = None,
                key: Optional[bytes] = None) -> int:
        """Send one message; returns its assigned absolute offset."""
        return self.produce_batch(topic, [message], partition=partition,
                                  key=key)

    def produce_batch(self, topic: str, messages: list[bytes],
                      partition: Optional[int] = None,
                      key: Optional[bytes] = None) -> int:
        """Send a batch to ONE partition; returns the first assigned
        offset. The batch rides a single RPC and as few device rounds as
        its size requires (vs. the reference's one message per RPC,
        PartitionClient.java:39).

        With idempotence on, the partition choice is PINNED for the
        whole call and every retry replays the same (pid, seq): an
        attempt whose response was lost but whose round committed is
        acked as a duplicate by the broker's dedup table — the window
        that used to make retried produces at-least-once. The sequence
        range is reserved the first time it goes on the wire; a call
        abandoned after that burns its range (see module docstring).

        With a `key`, the partition is resolved by KEY-HASH RANGE
        (elastic partitions): the request carries `key_hash` plus the
        resolver's `pgen` generation stamp, so a broker whose topology
        moved on fences it with `stale_partition_gen:` — this loop then
        re-resolves from the refusal's routing payload and retries
        under the new generation. A reroute reserves a FRESH sequence
        range (the new partition is a different log; the old range is
        burnt), so a reroute straddling an unknown-outcome attempt is
        at-least-once — exactly the retried-ack contract, never worse."""
        if not messages:
            raise ValueError("empty batch")
        root = NULL_SPAN
        if self.spans is not None:
            tid = derive_trace_id(self._pid_name,
                                  next(self._trace_counter))
            if sampled(tid, self._trace_sample_n):
                # Root context: parent span id 0 marks the trace root.
                root = self.spans.span("client.produce",
                                       TraceContext(tid, 0),
                                       {"topic": topic})
        run = self._retry.begin()
        pin = partition
        khash = None if key is None else key_hash(key)
        pid = seq = None
        n = len(messages)
        while run.attempt():
            t = self._meta.topic(topic)
            if t is None:
                run.note(f"unknown topic {topic!r}")
                self._refresh_quietly()
                continue
            if khash is not None and partition is None:
                # Keyed routing re-resolves per attempt: an adopted
                # stale_partition_gen payload (or a background refresh)
                # moves the pin to the range's CURRENT owner; the dedup
                # identity is re-reserved on reroute below.
                owner = self._meta.route_key(topic, khash)
                if owner is not None and owner != pin:
                    if pin is not None:
                        seq = None  # different log: fresh identity
                    pin = owner
            if pin is None:
                # One selector advance per CALL (not per attempt): a
                # retry must replay the same partition, or the dedup
                # identity — and the at-most-once-per-partition story —
                # dissolves across attempts.
                pin = self._selector.select(t)
            addr = self._meta.leader_addr(topic, pin)
            if addr is None:
                run.note(f"no leader known for {topic}[{pin}]")
                self._refresh_quietly()
                continue
            if self._idempotence and pid is None:
                pid = self._ensure_pid(addr, run)
            if pid is not None and seq is None:
                seq = self._reserve_seq(topic, pin, n)
            # The producer NAME rides every request (pid or not): its
            # prefix before the first "/" is the tenant key the broker's
            # SLO admission controller meters (slo/admission.py) — an
            # `overloaded:` refusal is retryable, and this loop's
            # jittered exponential backoff IS the client half of the
            # shed contract (retrying flat-out would defeat it).
            req = {"type": "produce", "topic": topic, "partition": pin,
                   "messages": list(messages), "producer": self._pid_name}
            if pid is not None:
                req["pid"], req["seq"] = pid, seq
            if khash is not None:
                req["key_hash"] = khash
                gen = self._meta.generation(topic, pin)
                if gen is not None:
                    req["pgen"] = gen
            # One client.rpc span per transport ATTEMPT, and its id (not
            # the root's) rides as tctx: the broker's rpc.recv then pairs
            # with the wire round trip for the skew estimate, not with
            # the whole retry loop.
            rpc = NULL_SPAN if self.spans is None else \
                self.spans.span("client.rpc", root.ctx)
            if rpc.ctx is not None:
                req["tctx"] = rpc.ctx.wire()
            try:
                resp = self._transport.call(
                    addr, req, timeout=run.clip(self._timeout),
                )
            except RpcError as e:
                rpc.end(error=type(e).__name__)
                run.note(str(e))
                self._refresh_quietly()
                continue
            rpc.end()
            if resp.get("ok"):
                self.last_partition = int(resp.get("routed_partition", pin))
                root.end(n=n)  # duration == client-measured ack latency
                return int(resp["base_offset"])
            err = str(resp.get("error", ""))
            run.note(err)
            if err == "not_leader":
                # Follow the hint next attempt via a metadata refresh; the
                # hint's addr is also directly usable when present.
                self._refresh_quietly()
                continue
            if err.startswith("stale_partition_gen:"):
                # Generation fence: re-resolve from the refusal's
                # routing payload (no metadata round) — the next
                # attempt re-routes at the top of the loop.
                if not self._meta.adopt_routing(
                        topic, resp.get("routing") or []):
                    self._refresh_quietly()
                continue
            if fatal_response_error(err):
                raise ProduceError(err)  # terminal
        raise ProduceError(f"produce to {topic} failed: {run.summary()}")

    # ------------------------------------------------- keyed, batching path

    def partition_for(self, topic: str, key: bytes) -> Optional[int]:
        """The partition `send(topic, ..., key)` would route to now
        (None: unknown topic)."""
        return self._meta.route_key(topic, key_hash(key))

    def send(self, topic: str, message: bytes, key: bytes) -> SendWaiter:
        """Queue one keyed message and return at once; the waiter gives
        its offset. The key resolves to its partition by key-hash range;
        the message joins that partition's open batch and leaves with
        the next produce.multi request to the partition's leader (module
        docstring). Messages of one key from one producer commit in
        send order, once."""
        if not message:
            raise ValueError("empty message")
        khash = key_hash(key)
        partition = self._meta.route_key(topic, khash)
        if partition is None:
            self._refresh_quietly()
            partition = self._meta.route_key(topic, khash)
            if partition is None:
                raise ProduceError(f"unknown topic {topic!r}")
        root = NULL_SPAN
        if self.spans is not None:
            tid = derive_trace_id(self._pid_name,
                                  next(self._trace_counter))
            if sampled(tid, self._trace_sample_n):
                root = self.spans.span("client.send", TraceContext(tid, 0),
                                       {"topic": topic})
        with self._acc_cond:
            if self._closing:
                raise ProduceError("producer is closed")
            part, idx, look = self._acc.append(
                topic, partition, message, khash, time.monotonic())
            if root.ctx is not None:
                if part.traces is None:
                    part.traces = []
                # [index, client.send, client.accumulate, client.produce]
                part.traces.append([idx, root, self.spans.span(
                    "client.accumulate", root.ctx), NULL_SPAN])
            if self._sender is None:
                self._sender = threading.Thread(
                    target=self._sender_loop, daemon=True,
                    name="producer-sender")
                self._sender.start()
            elif look and not self._acc.saturated():
                # Wake the sender only if it would otherwise look too
                # late for this part (it sleeps until the earliest
                # linger or request deadline it knows of).
                wake = self._sender_wake
                if wake is None or wake > part.t_first + self._acc.linger_s:
                    self._acc_cond.notify()
        return SendWaiter(part, idx, self._done_cond)

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Send everything queued without waiting out the linger, and
        wait until every part is acked or failed; False on timeout."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._acc_cond:
            self._acc.flushing = True
            self._acc_cond.notify()
        try:
            while True:
                with self._acc_cond:
                    if not self._acc.pending():
                        return True
                left = None if end is None else end - time.monotonic()
                if left is not None and left <= 0:
                    return False
                with self._done_cond:  # a missed notify costs one poll
                    self._done_cond.wait(0.05 if left is None
                                         else min(left, 0.05))
        finally:
            with self._acc_cond:
                self._acc.flushing = False

    def _sender_loop(self) -> None:
        acc, cond = self._acc, self._acc_cond
        while True:
            refresh = False
            with cond:
                now = time.monotonic()
                expired = [r for r in self._requests
                           if not r.finished and r.deadline <= now]
                reqs: dict = {}
                wake = None
                if not expired and not acc.saturated():
                    reqs, wake, lost = acc.drain(now, self._meta.leader_addr)
                    if lost:
                        # A ready part with no known leader: look the
                        # leader up again, once per backoff.
                        if now >= self._next_lookup:
                            refresh = True
                            self._next_lookup = \
                                now + self._retry.base_backoff_s
                        wake = self._next_lookup if wake is None \
                            else min(wake, self._next_lookup)
                if self._need_refresh:
                    self._need_refresh, refresh = False, True
                if not reqs and not expired and not refresh:
                    if self._closing and not acc.pending():
                        return
                    for r in self._requests:
                        if not r.finished:
                            wake = r.deadline if wake is None \
                                else min(wake, r.deadline)
                    self._sender_wake = wake
                    cond.wait(None if wake is None
                              else max(0.0, wake - now))
                    self._sender_wake = 0.0
                    continue
                sent = [_Request(addr, parts, now + self._timeout)
                        for addr, parts in reqs.items()]
                self._requests.extend(sent)
            for r in expired:
                self._finish(r, None, RpcTimeout(
                    f"{r.addr}: no response after {self._timeout}s"))
            if refresh:
                self._refresh_quietly()
            for r in sent:
                self._send_request(r)

    def _send_request(self, r: _Request) -> None:
        spans = self.spans
        pid = None
        if self._idempotence:
            pid = self._ensure_pid(r.addr, self._retry.begin())
        wire_parts = []
        traced = None
        for i, part in enumerate(r.parts):
            if part.run is None:
                part.run = self._retry.begin()
                part.run.next_delay()  # the first attempt
            if pid is not None and part.seq is None:
                part.seq = self._reserve_seq(part.topic, part.partition,
                                             len(part.messages))
            wp = {"topic": part.topic, "partition": part.partition,
                  "messages": part.messages,
                  "key_span": [min(part.khashes), max(part.khashes)]}
            if pid is not None:
                wp["seq"] = part.seq
            gen = self._meta.generation(part.topic, part.partition)
            if gen is not None:
                wp["pgen"] = gen
            wire_parts.append(wp)
            for t in part.traces or ():
                t[2].end()  # client.accumulate (a retry: ended already)
                t[2] = NULL_SPAN
                if t[3] is NULL_SPAN:
                    t[3] = spans.span("client.produce", t[1].ctx,
                                      {"n": len(part.messages)})
                if traced is None:
                    traced = (i, t[3].ctx)
        req = {"type": "produce.multi", "producer": self._pid_name,
               "parts": wire_parts}
        if pid is not None:
            req["pid"] = pid
        if traced is not None:
            # ONE context rides the request (its first sampled message:
            # the broker's spans hang under it); every other sampled
            # message of the request gets a copy of the client.rpc
            # interval at the response.
            r.rpc, r.tctx = spans.span("client.rpc", traced[1]), traced[1]
            req["tctx"], req["tpart"] = r.rpc.ctx.wire(), traced[0]
        call_async = getattr(self._transport, "call_async", None)
        try:
            if call_async is None:  # exotic custom transport: one at a time
                self._finish(r, self._transport.call(
                    r.addr, req, timeout=self._timeout), None)
                return
            r.fut = call_async(r.addr, req)
        except RpcError as e:
            self._finish(r, None, e)
            return
        r.fut.add_done_callback(lambda f, r=r: self._on_future(r, f))

    def _on_future(self, r: _Request, fut) -> None:
        # Runs on the transport's reader thread (or inline on an in-proc
        # transport): bookkeeping only, never an RPC.
        if fut.cancelled():
            return
        err = fut.exception()
        if err is not None and not isinstance(err, RpcError):
            err = RpcError(f"{type(err).__name__}: {err}")
        self._finish(r, None if err is not None else fut.result(), err)

    def _finish(self, r: _Request, resp: Optional[dict],
                err: Optional[Exception]) -> None:
        """Settle one request: every part is acked, failed for good,
        rerouted, or put back at the head of its partition to retry."""
        with self._acc_cond:
            if r.finished:
                return  # a timeout and a late response raced
            r.finished = True
            self._requests.remove(r)
        if err is not None and r.fut is not None:
            abandon = getattr(self._transport, "abandon", None)
            if abandon is not None:
                abandon(r.fut)
        r.rpc.end(**({"error": type(err).__name__} if err else {}))
        t_rpc = (r.rpc.t0, self.spans.clock() - r.rpc.t0) \
            if r.rpc.ctx is not None else None
        if err is None and not resp.get("ok"):
            # The request itself was refused (an older broker, a
            # malformed frame): every part shares the answer.
            results = [resp] * len(r.parts)
        elif err is None:
            results = list(resp.get("parts") or ())
            results += [{"ok": False, "error": "bad_request: no result "
                         "for this part"}] * (len(r.parts) - len(results))
        else:
            results = [{"ok": False, "error": str(err)}] * len(r.parts)
        now = time.monotonic()
        acked_ns = time.monotonic_ns()
        refresh = err is not None
        outcomes = []  # (part, "ack" | "fail" | "retry" | "reroute", delay)
        for part, res in zip(r.parts, results):
            if res.get("ok"):
                part.acked_ns = acked_ns
                outcomes.append((part, "ack", int(res["base_offset"])))
                continue
            error = str(res.get("error", "produce failed"))
            part.run.note(error)
            if err is None and fatal_response_error(error):
                outcomes.append((part, "fail", error))
                continue
            if error.startswith("stale_partition_gen:"):
                # Re-resolve from the refusal's own routing payload.
                if not self._meta.adopt_routing(
                        part.topic, res.get("routing") or []):
                    refresh = True
                delay = part.run.next_delay()
                if delay is not None:
                    outcomes.append((part, "reroute", now + delay))
                    continue
            else:
                refresh = refresh or error == "not_leader"
                delay = part.run.next_delay()
            if delay is None:
                outcomes.append((part, "fail",
                                 f"produce to {part.topic} failed: "
                                 f"{part.run.summary()}"))
            else:
                outcomes.append((part, "retry", now + delay))
        with self._acc_cond:
            self._acc.request_done(r.addr)
            for part, what, arg in outcomes:
                if what == "retry":
                    self._acc.retry(part, arg)
                elif what == "reroute":
                    self._reroute(part, arg)
                else:
                    if what == "ack":
                        part.base_offset = arg
                    else:
                        part.error = arg
                    self._acc.complete(part)
            if refresh:
                self._need_refresh = True
            self._acc_cond.notify()
        for part, what, arg in outcomes:
            for t in part.traces or ():
                if t_rpc is not None and t[3].ctx is not None \
                        and t[3].ctx is not r.tctx:
                    self.spans.span_at("client.rpc", t[3].ctx, *t_rpc)
                if what in ("ack", "fail", "reroute"):
                    extra = {} if what == "ack" else {"error": what}
                    t[3].end(**extra)
                    t[1].end(n=len(part.messages), **extra)
            if what != "retry":
                part.traces = None
        with self._done_cond:
            self._done_cond.notify_all()

    def _reroute(self, part: Part, not_before: float) -> None:
        """(under _acc_cond) the partition's key range moved: the refused
        part and everything queued behind it are re-split by the routing
        just adopted, oldest first, and go AHEAD of what their new
        partitions hold. A rerouted part is a new batch of another log:
        it takes a fresh sequence range (the old one is burnt), as
        produce_batch's reroute does."""
        fresh: dict[int, Part] = {}
        order: list[Part] = []
        for old in self._acc.take_partition(part):
            old.moved = []
            for msg, kh in zip(old.messages, old.khashes):
                owner = self._meta.route_key(old.topic, kh)
                if owner is None:
                    owner = old.partition
                new = fresh.get(owner)
                if new is None:
                    new = fresh[owner] = Part(old.topic, owner, old.t_first)
                    # Closed (nothing younger joins it), and under the
                    # refused part's retry budget and backoff.
                    new.retrying, new.not_before = True, not_before
                    new.run = part.run
                    order.append(new)
                old.moved.append((new, len(new.messages)))
                new.messages.append(msg)
                new.khashes.append(kh)
                new.nbytes += len(msg)
        self._acc.requeue_front(order)

    def _reserve_seq(self, topic: str, partition: int, n: int) -> int:
        """Reserve `n` sequence numbers for one batch (thread-safe).
        Reservation happens once per call, right before the identity
        first goes on the wire; retries replay it, abandonment burns it."""
        with self._seq_lock:
            seq = self._seqs.get((topic, partition), 0)
            self._seqs[(topic, partition)] = seq + n
        return seq

    def _ensure_pid(self, addr: str, run) -> Optional[int]:
        """Register this producer's id (once) with the metadata plane,
        then RE-register at pid_refresh_s cadence — registration of an
        existing name is the session refresh keeping the pid out of the
        reaper's idle window (ClusterConfig.pid_retention_s). None on
        initial-registration failure — the current call proceeds
        unstamped (at-least-once, the pre-idempotence contract) and the
        next call tries again; a FAILED refresh keeps the cached pid
        (best-effort: the pid stays valid until actually reaped, and a
        reaped pid only costs the dedup window, never safety)."""
        now = time.monotonic()
        if self._pid is not None:
            if (self._pid_refresh_s <= 0
                    or now - self._pid_registered_t < self._pid_refresh_s):
                return self._pid
            # Attempting a refresh: stamp the attempt BEFORE the RPC so
            # a failing metadata plane costs one extra RPC per refresh
            # WINDOW, not one per produce (the original registration's
            # never-wedge-the-produce-path rule applies to refreshes
            # too; the cached pid stays valid until actually reaped).
            self._pid_registered_t = now
        try:
            resp = self._transport.call(
                addr,
                {"type": "producer.register", "name": self._pid_name},
                timeout=run.clip(self._timeout),
            )
        except RpcError as e:
            run.note(f"pid registration: {e}")
            return self._pid
        if resp.get("ok"):
            self._pid = int(resp["pid"])
            self._pid_registered_t = now
            return self._pid
        run.note(f"pid registration: {resp.get('error')}")
        return self._pid

    def produce_batch_async(self, topic: str, messages: list[bytes],
                            partition: Optional[int] = None):
        """Pipelined produce: returns a waiter `() -> int` (first
        assigned offset). Many batches can be in flight per connection —
        frames carry request ids, so an in-flight batch costs one
        pending future, never a thread (the in-proc transport serves the
        same `call_async` surface with an inline-resolved future; no
        transport wraps a sync call in a pool thread). The waiter
        follows ONE not_leader hint with a pipelined re-send; any other
        failure raises ProduceError and the caller decides (a windowed
        sender usually just re-sends)."""
        if not messages:
            raise ValueError("empty batch")
        call_async = getattr(self._transport, "call_async", None)
        if call_async is None:  # exotic custom transport: stay sync
            resp_val = self.produce_batch(topic, messages,
                                          partition=partition)
            return lambda: resp_val
        t = self._meta.topic(topic)
        if t is None:
            raise ProduceError(f"unknown topic {topic!r}")
        pid = self._selector.select(t) if partition is None else partition
        addr = self._meta.leader_addr(topic, pid)
        if addr is None:
            raise ProduceError(f"no leader known for {topic}[{pid}]")
        req = {"type": "produce", "topic": topic, "partition": pid,
               "messages": list(messages), "producer": self._pid_name}
        if self._idempotence:
            if self._pid is None:
                # One synchronous registration RPC on the first window;
                # every later batch stamps from the cached pid. Failure
                # leaves this batch unstamped (at-least-once), same as
                # the sync path.
                self._ensure_pid(addr, self._retry.begin())
            if self._pid is not None:
                req["pid"] = self._pid
                req["seq"] = self._reserve_seq(topic, pid, len(messages))
        fut = call_async(addr, req)

        def wait() -> int:
            resp = fut.result(timeout=self._timeout)
            if not resp.get("ok") and resp.get("error") == "not_leader":
                # Leadership moved under the window: one pipelined
                # re-send at the hinted leader (refresh so later
                # batches route straight there).
                self._refresh_quietly()
                addr2 = resp.get("leader_addr") or self._meta.leader_addr(
                    topic, pid
                )
                if addr2:
                    resp = call_async(addr2, req).result(
                        timeout=self._timeout
                    )
            if not resp.get("ok"):
                raise ProduceError(str(resp.get("error", "produce failed")))
            return int(resp["base_offset"])

        return wait

    def close(self, timeout: float = 10.0) -> None:
        """Flushes what `send()` queued (up to `timeout` seconds), stops
        the sender thread, then closes metadata and transport."""
        if self._sender is not None:
            self.flush(timeout)
            with self._acc_cond:
                self._closing = True
                self._acc_cond.notify()
            self._sender.join(timeout=2.0)
        self._meta.close()
        if self._owns_transport:
            self._transport.close()

    def _refresh_quietly(self) -> None:
        try:
            self._meta.refresh()
        except MetadataError:
            pass
