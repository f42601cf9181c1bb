"""ConsumerClient: consume(topic) with server-tracked offsets.

API parity with the reference's ConsumerClientImpl (reference:
mq-common/src/main/java/client/ConsumerClientImpl.java:62-117): each
consume() picks ONE partition round-robin, reads up to max_messages
(default 10 — `:21`), and with auto_commit=True (the reference's
hardwired behavior, commit at `:103-109`) immediately commits
offset + n — at-most-once delivery. auto_commit=False flips to
at-least-once: process, then call commit() yourself.

Readahead (`prefetch` > 0, needs a pipelining transport): the client
keeps a SESSION with each leader. It remembers the (topic, partition)s
its caller polls, each with its position (the `next_offset` of its last
answer; unknown until the first one, when the broker's committed offset
decides), and when a poll finds no answer in hand it sends ONE
`consume.multi` to that partition's leader: for the partition asked for
and for every other partition of the session on that leader whose last
answer has been handed out and which the caller, going by its last
round, will ask for within `_ANSWER_MAX_AGE_S` - each at its own
position with its own `max_messages`. The answers are kept and handed
out as the caller comes round: an answer is handed out once, one that is
never collected is not fetched again and moves no position, and a
partition the caller stops polling leaves the session. A caller that
rotates over 32 partitions of one leader 8 ms apart sends a request
every eighth poll instead of every poll; one that drains them in a tight
loop sends one a rotation. The age limit is what keeps that free for the
messages: an answer waits for its hand-out no longer than a produce takes
to be acked, where a whole rotation's answers fetched at once would be
half a rotation old on average (PERF.md section 6, PR 40, has the
arithmetic and the 1 KB cell that would have paid for it).
"Within" is counted on the CALLER's clock: the wall clock less the
seconds the caller has spent inside `consume_with_position` - in the
session's own synchronous fetch, a commit flush, the single-partition
path with its retries and back-off. That clock runs while the caller
sleeps, works through its messages or walks to its next call, and stands
while the client holds it, so the session's width follows how the CALLER
goes round and not how long a request took. On the wall clock a request
slower than `_ANSWER_MAX_AGE_S` made every partition of a tight loop
read as "polled more than that apart": every fetch then carried one
part, and a rotation of 32 partitions was 32 blocking requests of that
length with no way back (PERF.md section 6, PR 45: the keyed cell). The
price is stated there too: an answer in hand can be older than
`_ANSWER_MAX_AGE_S` of wall time when a sibling's fetch (another
leader's) blocks between the fetch that brought it and its hand-out.
Delivered offsets are committed the same way: ONE `offset.commit.multi`
per leader, sent asynchronously when an answer with messages is handed
out and with every fetch, ONE in flight per (consumer, leader) and the
newest offsets parked behind it (per partition they only grow) - the
broker's worker pool does not keep a connection's order and its offset
table takes the last writer; a part that failed is re-driven
synchronously through `commit()` before anything newer of its partition
is sent. A part the broker refuses (`not_leader`, a stale generation, a
lost quorum) falls to the single-partition `consume`, which re-resolves
with the retry policy, while its siblings are served. The contract
shift when prefetch is on: commits are acknowledged ASYNCHRONOUSLY
(flushed on close()/flush_commits()), so delivery runs ahead of the
committed offset - a crash between delivery and commit flush
re-delivers, i.e. prefetch trades the strict at-most-once auto-commit
for at-least-once pipelining. A committed offset never passes what was
handed to the caller and never moves back.

`long_poll_s` > 0 makes the session TAIL (a Kafka consumer's
`fetch.min.bytes=1` / `fetch.max.wait.ms`): ONE long-polling
`consume.multi` per leader is kept in flight, asynchronously, listing
every partition of the session there that has no answer in hand, each at
its position, with `wait_s`; the broker parks it once, for all its
parts, and answers when rows settle past the position of ANY of them, or
after `long_poll_s`, empty (broker/server.py `_fetch`). `consume(topic,
p)` hands out what is in hand for `p` or returns empty AT ONCE - a poll
never stands on a park, its own partition's or a sibling's - and the
fetch goes out again as soon as its answer has been handed out
(`_arm`), so a delivery costs one RPC, not one per poll. The first poll
of a partition is the plain session's synchronous fetch (no `wait_s`:
the broker's committed offset decides its position); an
empty-but-advanced answer moves the position; a refused part, and every
part of a failed request, takes the single-partition `consume`. With
`long_poll_s` 0 no request carries a `wait_s` key. Commits ride the same
per-leader pipeline either way.

With `follower_reads` a readahead client keeps ONE `consume` per
partition in flight at an explicit offset instead of a session (`_pf`;
a `consume.multi` is not served by a follower), long-polling it after
an empty window if `long_poll_s` is set; its commits ride the same
per-leader pipeline. Every lever is opt-in and independently A/B-able
against the legacy one-RPC-per-call behavior.

Rack-aware reads (`client_rack="<rack>"`, Kafka's KIP-392
`client.rack`; needs a cluster with `follower_reads` and `broker_racks`,
and readahead): the SESSION above - and with `long_poll_s` its parked
fetch - is kept with the leased follower of the client's own rack
instead of each partition's leader, while one exists
(`MetadataManager.rack_follower`: sticky, not a draw a call): every
partition of the session in ONE `consume.multi` marked `follower_ok`,
which that standby serves whole from its follower read plane and parks
on its own settled floor (broker/server.py `_follower_fetch`). This is
the mode for a consumer that TAILS from the in-rack replica. The first
read of a partition (the broker's committed offset decides its
position), a part the follower refuses (`not_settled_here:`) and every
commit go to the leader. A follower that stops answering, loses its
lease or changes epoch costs one metadata refresh and a fall back to the
leaders for `_RACK_RETRY_S`, never an error to the caller; positions are
the client's own and an answer counts only for a part still where the
fetch was sent for, so what `consume` hands out has no gap and no repeat
whoever served it. `client_rack` takes the session and leaves
`follower_reads` (below) without effect; a client without it behaves as
before.

Follower reads (`follower_reads=True`, needs a cluster running with the
broker-side knob on; the BACKLOG fan-out mode, for many cursors catching
up - a tailing consumer wants `client_rack` above): EXPLICIT-OFFSET
reads route to a standby broker
holding a current-epoch follower-read lease (meta.topics advertises the
lease table), spreading a backlog fan-out over the standby set instead
of funneling every cursor through the leader. Safety lives broker-side
(broker/follower.py: a follower only answers strictly below its
replicated settled floor, refusing with retryable `not_settled_here:`),
so the client policy is pure routing: go to a follower only when the
last window came back FULL (backlog evidence — tail polls would just
bounce off the floor), fall back to the leader on any refusal, and send
commits to the leader always. Reads with no tracked position (the first
call, or after a pipeline break) go to the leader, which owns the
server-tracked offset table.
"""

from __future__ import annotations

import itertools
import time
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Optional

from ripplemq_tpu.client.metadata import MetadataError, MetadataManager
from ripplemq_tpu.obs.spans import (
    NULL_SPAN,
    SpanRing,
    TraceContext,
    derive_trace_id,
    sampled,
)
from ripplemq_tpu.client.selector import PartitionSelector, RoundRobinSelector
from ripplemq_tpu.wire.retry import RetryPolicy, fatal_response_error
from ripplemq_tpu.wire.transport import RpcError, TcpClient, Transport

DEFAULT_MAX_MESSAGES = 10  # ConsumerClientImpl.java:21

# How old an answer of the session may be when it is handed out: a fetch
# carries, beside the partition asked for, those the caller will ask for
# within this long (going by when it asked last round). A message then
# waits in the client no longer than its produce took to be acked; with
# no limit a rotation's answers are half a rotation old on average.
# Counted on the caller's clock (`_Part.polled`; module docstring): the
# time the client's own requests held the caller is not the caller's
# cadence, and counting it made a request slower than this a cliff - one
# part a request from then on. The parked fetch (`_arm`), which never
# holds its caller, reads it on the wall clock.
_ANSWER_MAX_AGE_S = 0.06
# A partition not polled for this long leaves the session: wall time
# (`_Part.seen`), since it is about a caller that went away.
_SESSION_IDLE_S = 5.0
# How long a rack-aware client stays with the leaders after its rack's
# follower failed it (`_rack_failed`) before it looks at the lease table
# again.
_RACK_RETRY_S = 2.0


class ConsumeError(Exception):
    pass


class _Part:
    """One (topic, partition) of the session."""

    __slots__ = ("pos", "limit", "addr", "answer", "polled", "seen",
                 "fetching", "joined", "via_follower")

    def __init__(self, limit: int, addr: Optional[str], now: float,
                 asked: float) -> None:
        self.pos: Optional[int] = None  # next read position; None: the
        #                                 broker's committed offset decides
        self.limit = limit              # the caller's max_messages
        self.addr = addr                # where its session is: its
        #                                 leader, or the client's in-rack
        #                                 follower; None: the single-
        #                                 partition path re-resolves it
        self.via_follower = False       # `answer` came from a follower
        self.answer: Optional[tuple[list, int, int]] = None  # not handed
        #                       out yet: (messages, offset, next_offset)
        self.polled = asked             # when the caller last asked, on
        #                                 the caller's clock
        self.seen = now                 # ... and on the wall clock
        self.fetching = False           # listed in the leader's parked
        #                                 fetch in flight (long_poll_s)
        self.joined = now               # when it entered the session


class _Parked:
    """The ONE long-polling consume.multi in flight to a leader: its
    future, the parts it lists - each with the position and window it
    was sent for - when it went out, and its client.rpc span."""

    __slots__ = ("fut", "parts", "sent", "rpc", "follower")

    def __init__(self, fut, parts: list, sent: float, rpc,
                 follower: bool = False) -> None:
        self.fut, self.parts, self.sent, self.rpc = fut, parts, sent, rpc
        self.follower = follower  # sent to the in-rack follower


class _LeaderCommits:
    """The commit pipeline to one leader: the offsets of the ONE
    offset.commit.multi in flight, and the newest offsets parked behind
    it, both by (topic, partition)."""

    __slots__ = ("owed", "sent", "fut")

    def __init__(self) -> None:
        self.owed: dict[tuple[str, int], int] = {}
        self.sent: dict[tuple[str, int], int] = {}
        self.fut = None


class ConsumerClient:
    def __init__(
        self,
        bootstrap: list[str],
        consumer_id: str,
        transport: Optional[Transport] = None,
        selector: Optional[PartitionSelector] = None,
        auto_commit: bool = True,
        max_messages: int = DEFAULT_MAX_MESSAGES,
        metadata_refresh_s: float = 10.0,
        rpc_timeout_s: float = 5.0,
        retries: int = 3,
        retry_backoff_s: float = 0.2,
        deadline_s: Optional[float] = None,
        retry_policy: Optional[RetryPolicy] = None,
        prefetch: int = 0,
        long_poll_s: float = 0.0,
        follower_reads: bool = False,
        trace_sample_n: int = 0,
        client_rack: Optional[str] = None,
    ) -> None:
        self._transport = transport if transport is not None else TcpClient()
        self._owns_transport = transport is None
        self._selector = selector or RoundRobinSelector()
        self.consumer_id = consumer_id
        self.auto_commit = auto_commit
        self.max_messages = max_messages
        self.prefetch = max(0, int(prefetch))
        self.long_poll_s = max(0.0, float(long_poll_s))
        # Rack-aware session (module docstring); it takes the session,
        # so the per-partition `_pf` mode of follower_reads is off.
        self.client_rack = str(client_rack) if client_rack else None
        self._rack_down_until = 0.0
        self.follower_reads = bool(follower_reads) and not self.client_rack
        self._timeout = rpc_timeout_s
        # Follower routing's position hint: last delivered next_offset
        # per (topic, partition). Only a HINT — the leader's
        # server-tracked offset stays authoritative whenever routing
        # falls back to it.
        self._pos: dict[tuple[str, int], int] = {}
        # Routing forensics: how many deliveries a follower actually
        # served (vs leader fallback), and whether the LAST one did —
        # the chaos workload tags its history ops with this so a run's
        # verdict can say how much fan-out the follower plane absorbed.
        self.follower_served = 0
        self.last_from_follower = False
        # Readahead state. The session (module docstring): what the
        # caller polls, by (topic, partition); `_parked`: with
        # long_poll_s, its one long-polling consume.multi in flight per
        # leader. `_pf`: with follower_reads instead, the one `consume`
        # in flight per partition at an explicit offset. `_commits`:
        # the async auto-commits of both, by leader address.
        self._sess: dict[tuple[str, int], _Part] = {}
        self._parked: dict[str, _Parked] = {}  # by leader (long_poll_s)
        self._pf: dict[tuple[str, int], dict] = {}
        self._commits: dict[str, _LeaderCommits] = {}
        self._clock = time.monotonic
        # The caller's clock (module docstring): `_held_s` is the wall
        # time the caller has spent inside consume_with_position,
        # `_asked` the current call's entry on the clock that leaves it
        # out.
        self._held_s = 0.0
        self._asked = 0.0
        # Causal tracing (obs/spans.py), mirroring ProducerClient: every
        # trace_sample_n-th consume opens a client.consume root span
        # whose context rides `tctx` on the sync and follower fetches
        # and on the session's consume.multi - a parked one carries the
        # context of the call that sent it and its client.rpc ends when
        # the answer is taken (`_pf` fetches were armed before this
        # call existed, so they stay unstamped). `spans` is public for
        # the assembler.
        self._trace_sample_n = int(trace_sample_n)
        self._trace_counter = itertools.count()
        self.spans: Optional[SpanRing] = (
            SpanRing(consumer_id) if self._trace_sample_n > 0 else None
        )
        self._trace_root = NULL_SPAN  # current call's root (single-threaded)
        # Unified retry discipline (wire/retry.py): jittered exponential
        # backoff, optional per-operation deadline budget.
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

    # ------------------------------------------------------------------ API

    def consume(
        self,
        topic: str,
        partition: Optional[int] = None,
        max_messages: Optional[int] = None,
    ) -> list[bytes]:
        """Read from one (round-robin-chosen) partition of `topic`."""
        msgs, _, _, _ = self.consume_with_position(topic, partition, max_messages)
        return msgs

    def _session_on(self, call_async) -> bool:
        """Whether readahead runs as a session with each leader: read
        off the client's own input (module docstring)."""
        return (self.prefetch > 0 and call_async is not None
                and not self.follower_reads)

    def consume_with_position(
        self,
        topic: str,
        partition: Optional[int] = None,
        max_messages: Optional[int] = None,
    ) -> tuple[list[bytes], int, int, int]:
        """Like consume(), also returning (messages, partition, offset,
        next_offset). Manual committers commit `next_offset` — offsets are
        STORAGE offsets (the broker pads replication rounds for the TPU's
        alignment), so `offset + len(messages)` is NOT a valid position."""
        entered = self._clock()
        self._asked = entered - self._held_s
        try:
            return self._consume(topic, partition, max_messages)
        finally:
            # However the call ends, the caller was held this long.
            self._held_s += self._clock() - entered

    def _consume(self, topic: str, partition: Optional[int],
                 max_messages: Optional[int]):
        limit = self.max_messages if max_messages is None else max_messages
        self.last_from_follower = False
        root = NULL_SPAN
        if self.spans is not None:
            tid = derive_trace_id(self.consumer_id,
                                  next(self._trace_counter))
            if sampled(tid, self._trace_sample_n):
                root = self.spans.span("client.consume",
                                       TraceContext(tid, 0),
                                       {"topic": topic})
        self._trace_root = root
        call_async = getattr(self._transport, "call_async", None)
        session = self._session_on(call_async)
        if self.prefetch > 0 and call_async is not None:
            # Pin the round-robin choice ONCE per call: the readahead
            # probe and the sync fallback below each advancing the
            # stateful selector would desynchronize readahead state
            # from delivered partitions (with an even partition
            # count the two paths alternate in lockstep and some
            # partitions are never consumed at all).
            if partition is None:
                t = self._meta.topic(topic)
                if t is not None:
                    partition = self._selector.select(t)
            got = (self._consume_session if session
                   else self._consume_prefetched)(
                topic, partition, limit, call_async)
            if got is not None:
                root.end(n=len(got[0]))
                return got
        if self.follower_reads:
            if partition is None:
                # Same single-selector-advance pinning as the prefetch
                # probe above (and idempotent with it).
                t = self._meta.topic(topic)
                if t is not None:
                    partition = self._selector.select(t)
            got = self._consume_follower(topic, partition, limit, call_async)
            if got is not None:
                root.end(n=len(got[0]))
                return got
        run = self._retry.begin()
        while run.attempt():
            t = self._meta.topic(topic)
            if t is None:
                run.note(f"unknown topic {topic!r}")
                self._refresh_quietly()
                continue
            pid = self._selector.select(t) if partition is None else partition
            addr = self._meta.leader_addr(topic, pid)
            if addr is None:
                run.note(f"no leader known for {topic}[{pid}]")
                self._refresh_quietly()
                continue
            req = {"type": "consume", "topic": topic, "partition": pid,
                   "consumer": self.consumer_id, "max_messages": limit}
            part = self._sess.get((topic, pid)) if session else None
            if part is not None and part.pos is not None:
                # A session's partition on the single path (its part was
                # refused, or the fetch failed): at its own position.
                req["offset"] = part.pos
            else:
                # A readahead fallback must not race its own unflushed
                # commits: the server-tracked offset lags until they
                # apply.
                self._flush_commit_key(topic, pid)
            # Per-ATTEMPT client.rpc span (its id rides as tctx): the
            # broker's rpc.recv pairs with the wire round trip for the
            # skew estimate, not with the retry loop (producer twin).
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
                msgs = list(resp["messages"])
                offset = int(resp["offset"])
                next_offset = int(resp.get("next_offset", offset))
                got = self._deliver(topic, pid, addr, limit, call_async,
                                    msgs, offset, next_offset)
                root.end(n=len(msgs))
                return got
            err = str(resp.get("error", ""))
            run.note(err)
            if err == "not_leader":
                self._refresh_quietly()
                continue
            if fatal_response_error(err):
                raise ConsumeError(err)
        raise ConsumeError(f"consume from {topic} failed: {run.summary()}")

    # ------------------------------------------------ session with a leader

    def _consume_session(self, topic: str, partition: Optional[int],
                         limit: int, call_async):
        """Serve one consume from the session: hand out the answer in
        hand, or fetch first - ONE consume.multi to the partition's
        leader (`_fetch`). Returns None to fall back to the
        single-partition path (which re-resolves leadership with the
        retry policy). The caller pins `partition` before calling (one
        selector advance per consume)."""
        if partition is None:
            return None  # topic unknown: the sync path resolves it
        now, asked = self._clock(), self._asked
        key = (topic, partition)
        part = self._sess.get(key)
        if part is None:
            part = self._sess[key] = _Part(
                limit, self._meta.leader_addr(topic, partition), now, asked)
            before = None
        else:
            before, part.polled, part.seen = part.polled, asked, now
            if part.limit != limit:
                # In hand is an answer cut for another window: dropped,
                # and like any answer never handed out it moved nothing.
                part.limit, part.answer = limit, None
        if self.long_poll_s > 0 and part.pos is not None:
            # Tailing: the leader's parked fetch brings the answers. A
            # partition with none in hand reads empty AT ONCE - it never
            # stands on a park, its own or a sibling's.
            if part.addr is not None:
                self._absorb(part.addr, now)
            if part.answer is None and part.addr is not None:
                self._arm(part.addr, now, call_async)
                return [], partition, part.pos, part.pos
        elif part.answer is None and part.addr is not None:
            self._fetch(key, part, before, now, call_async)
        if part.answer is None:
            return None
        msgs, offset, next_offset = part.answer
        if part.via_follower:
            part.via_follower = False
            if msgs:
                self.follower_served += 1
                self.last_from_follower = True
        return self._deliver(topic, partition, part.addr, limit, call_async,
                             msgs, offset, next_offset)

    # ------------------------------------------- the in-rack follower

    def _rack_addr(self, now: float) -> Optional[str]:
        """Where a rack-aware client's session is kept: the leased
        follower of its rack, None without one (or without a rack, or
        while the follower is held to have failed)."""
        if self.client_rack is None or now < self._rack_down_until:
            return None
        return self._meta.rack_follower(self.client_rack)

    def _rack_failed(self, addr: str) -> None:
        """The in-rack follower did not answer, or answered as a broker
        without a lease does: its partitions go back through the
        single-partition path to their leaders, the session stays with
        the leaders for `_RACK_RETRY_S`, and one refresh fetches the
        lease table that says why."""
        self._rack_down_until = self._clock() + _RACK_RETRY_S
        for q in self._sess.values():
            if q.addr == addr:
                q.addr = None
        self._refresh_quietly()

    def _fetch(self, key: tuple[str, int], part: _Part,
               before: Optional[float], now: float, call_async) -> None:
        """ONE consume.multi to `part`'s leader: for `part`, and for
        every other partition of the session on that leader whose last
        answer has been handed out, whose position is known, and which
        the caller asked for within `_ANSWER_MAX_AGE_S` after it last
        asked for `part` (`before`) - so will again, if it goes round as
        it did. `before` and `polled` are stamps of the caller's clock
        (module docstring): the time this very request holds the caller
        is not part of how the caller goes round, so a late caller
        polling back to back gets its whole rotation on this leader in
        one request however long a request takes. `now` is wall time,
        for the idle rule. The answers are kept in the session; a
        refused part's partition is left to the single-partition path.
        The leader's parked commits go out with the fetch."""
        addr = part.addr
        if part.pos is None:
            # The broker's committed offset decides where this read
            # starts: everything handed out before must have landed.
            self._flush_commit_key(*key)
        parts = [(key, part)]
        idle = []
        for k, q in self._sess.items():
            if q is part:
                continue
            if now - q.seen > _SESSION_IDLE_S:
                idle.append(k)  # no longer polled: leaves the session
            elif (before is not None and q.answer is None
                    and not q.fetching
                    and q.addr == addr and q.pos is not None
                    and 0 <= q.polled - before <= _ANSWER_MAX_AGE_S):
                parts.append((k, q))
        for k in idle:
            del self._sess[k]
        req = {"type": "consume.multi", "consumer": self.consumer_id,
               "parts": [
                   {"topic": k[0], "partition": k[1],
                    "max_messages": q.limit,
                    **({} if q.pos is None else {"offset": q.pos})}
                   for k, q in parts]}
        # To the in-rack follower only at explicit offsets: a first
        # read is its leader's, which `addr` then is.
        follower = (addr == self._rack_addr(now)
                    and all(q.pos is not None for _, q in parts))
        if follower:
            req["follower_ok"] = True
        self._drive_commits(addr, call_async)
        rpc = NULL_SPAN if self.spans is None else \
            self.spans.span("client.rpc", self._trace_root.ctx)
        if rpc.ctx is not None:
            req["tctx"] = rpc.ctx.wire()
        try:
            resp = self._transport.call(addr, req, timeout=self._timeout)
        except RpcError as e:
            rpc.end(error=type(e).__name__)
            if follower:
                self._rack_failed(addr)
            return
        rpc.end()
        answers = resp.get("parts") if resp.get("ok") else None
        if not isinstance(answers, list) or len(answers) != len(parts):
            answers = None
        if follower and (answers is None or not resp.get("follower")):
            self._rack_failed(addr)
        if answers is None:
            return
        for (_, q), ans in zip(parts, answers):
            if ans.get("ok"):
                offset = int(ans["offset"])
                q.answer = (list(ans["messages"]), offset,
                            int(ans.get("next_offset", offset)))
                q.via_follower = bool(resp.get("follower"))
            else:
                q.addr = None  # refused: its next poll goes the single path

    # ------------------------------------- the parked fetch (long_poll_s)

    def _arm(self, addr: str, now: float, call_async) -> None:
        """Send the leader at `addr` its ONE long-polling consume.multi,
        unless one is in flight: for every partition of the session
        there whose position is known and which has no answer in hand,
        each at its position, with `wait_s` - the broker answers when
        rows settle past ANY of them, or after `long_poll_s`, empty. A
        partition with an answer still to be handed out cannot be listed
        (its position moves at the hand-out), and a fetch parked without
        it would not see its next rows: while the caller is coming round
        for such an answer (it asked within `_ANSWER_MAX_AGE_S`) the
        fetch waits for the hand-out, which sends it (`_deliver`). (Wall
        time, here and below: a parked fetch never holds its caller, and
        `now` is also when the fetch went out, for its timeout.) While
        the session is still learning what its caller polls (a partition
        joined it within `_ANSWER_MAX_AGE_S`) a fetch waits no longer
        than that: the first ones list a partition or two, and one of
        them parked for the whole `long_poll_s` would keep the leader's
        one fetch away from every partition that joined after it. The
        leader's parked commits go out with the fetch."""
        if addr in self._parked:
            return
        parts, idle = [], []
        wait_s = self.long_poll_s
        for k, q in self._sess.items():
            if q.addr != addr:
                continue
            if now - q.joined <= _ANSWER_MAX_AGE_S:
                wait_s = min(wait_s, _ANSWER_MAX_AGE_S)
            if q.pos is None:
                continue
            if now - q.seen > _SESSION_IDLE_S:
                idle.append(k)  # no longer polled: leaves the session
            elif q.answer is None:
                parts.append((k, q, q.pos, q.limit))
            elif now - q.seen <= _ANSWER_MAX_AGE_S:
                return
        for k in idle:
            del self._sess[k]
        if not parts:
            return
        req = {"type": "consume.multi", "consumer": self.consumer_id,
               "wait_s": wait_s,
               "parts": [{"topic": k[0], "partition": k[1],
                          "max_messages": limit, "offset": pos}
                         for k, _, pos, limit in parts]}
        follower = addr == self._rack_addr(now)
        if follower:
            # The in-rack follower serves it whole from its own plane
            # and parks it on its own settled floor; every leader's
            # parked commits go out with it, since none has a fetch.
            req["follower_ok"] = True
        for a in list(self._commits) if follower else (addr,):
            self._drive_commits(a, call_async)
        rpc = NULL_SPAN if self.spans is None else \
            self.spans.span("client.rpc", self._trace_root.ctx)
        if rpc.ctx is not None:
            req["tctx"] = rpc.ctx.wire()
        try:
            fut = call_async(addr, req)
        except RpcError as e:
            rpc.end(error=type(e).__name__)
            for _, q, _, _ in parts:
                q.addr = None  # the single-partition path re-resolves
            if follower:
                self._rack_failed(addr)
            return
        for _, q, _, _ in parts:
            q.fetching = True
        self._parked[addr] = _Parked(fut, parts, now, rpc, follower)

    def _absorb(self, addr: str, now: float) -> None:
        """Take the answer of the leader's parked fetch, if it has come:
        rows are kept to be handed out, an empty-but-advanced answer
        moves the position, a refused part (or every part of a failed
        request, or of one that outlived its wait and the RPC timeout)
        is left to the single-partition path. An answer counts only for
        a part still where it was when the fetch went out."""
        f = self._parked.get(addr)
        if f is None:
            return
        if f.fut.done():
            try:
                resp = f.fut.result(timeout=0)
            except Exception:
                resp = {}
        elif now - f.sent > self.long_poll_s + self._timeout:
            # never answered: the wait it carried and an RPC's time over
            self._abandon(f.fut)
            resp = {}
        else:
            return
        del self._parked[addr]
        f.rpc.end()
        answers = resp.get("parts") if resp.get("ok") else None
        if not isinstance(answers, list) or len(answers) != len(f.parts):
            answers = [{}] * len(f.parts)
            resp = {}
        if f.follower and not resp.get("follower"):
            # No answer, a refusal of the whole request (its park was
            # released: the lease went, the epoch moved, the broker
            # stops), or the answer of a broker without a lease.
            self._rack_failed(addr)
        for (k, q, pos, limit), ans in zip(f.parts, answers):
            q.fetching = False
            if (self._sess.get(k) is not q or q.pos != pos
                    or q.limit != limit or q.answer is not None):
                continue
            if not ans.get("ok"):
                q.addr = None
                continue
            offset = int(ans["offset"])
            next_offset = int(ans.get("next_offset", offset))
            msgs = list(ans["messages"])
            if msgs:
                q.answer = (msgs, offset, next_offset)
                q.via_follower = bool(resp.get("follower"))
            else:
                q.pos = max(pos, next_offset)

    def _abandon(self, fut) -> None:
        """Give up on a fetch in flight, where the transport keeps a
        pending entry for it (`TcpClient.abandon`)."""
        abandon = getattr(self._transport, "abandon", None)
        if abandon is not None:
            abandon(fut)

    # --------------------------------- one fetch in flight per partition

    def _consume_prefetched(self, topic: str, partition: Optional[int],
                            limit: int, call_async):
        """Serve one consume from the in-flight readahead fetch, if one
        is armed and healthy. Returns None to fall back to the sync
        path (which re-resolves leadership with the retry policy). The
        caller pins `partition` before calling (one selector advance
        per consume)."""
        if partition is None:
            return None  # topic unknown: the sync path resolves it
        pid = partition
        st = self._pf.pop((topic, pid), None)
        if st is None or st["limit"] != limit:
            return None
        try:
            resp = st["fut"].result(
                timeout=self._timeout + st.get("wait_s", 0.0)
            )
        except (TimeoutError, FuturesTimeoutError, RpcError, OSError):
            return None  # pipeline broken: sync path re-resolves
        if not resp.get("ok"):
            return None  # not_leader/refusal: sync path handles + retries
        msgs = list(resp["messages"])
        offset = st["offset"]
        next_offset = int(resp.get("next_offset", offset))
        if resp.get("follower"):
            self.follower_served += 1
            self.last_from_follower = True
        return self._deliver(topic, pid, st["addr"], limit, call_async,
                             msgs, offset, next_offset)

    # ---------------------------------------------------- follower reads

    def _consume_follower(self, topic: str, partition: Optional[int],
                          limit: int, call_async):
        """One explicit-offset read against a leased follower. Returns
        None (routing miss, refusal, transport error, or an empty
        answer) to fall back to the leader path — never an error: the
        leader serves everything a follower can and more."""
        if partition is None:
            return None
        pid = partition
        pos = self._pos.get((topic, pid))
        if pos is None:
            return None  # no tracked position: the leader resolves it
        addr = self._meta.follower_addr()
        if addr is None:
            return None
        # Same guard as the sync path: an explicit-offset read must not
        # race this partition's own unflushed async commit.
        self._flush_commit_key(topic, pid)
        req = {"type": "consume", "topic": topic, "partition": pid,
               "consumer": self.consumer_id, "max_messages": limit,
               "offset": int(pos), "follower_ok": True}
        rpc = NULL_SPAN if self.spans is None else \
            self.spans.span("client.rpc", self._trace_root.ctx)
        if rpc.ctx is not None:
            req["tctx"] = rpc.ctx.wire()
        try:
            resp = self._transport.call(addr, req, timeout=self._timeout)
        except RpcError:
            rpc.end(error="rpc")
            return None
        rpc.end()
        if not resp.get("ok") or not resp.get("follower"):
            return None  # not_settled_here / deposed: leader fallback
        msgs = list(resp["messages"])
        if not msgs:
            return None  # gap skip or dry window: let the leader decide
        offset = int(resp["offset"])
        next_offset = int(resp.get("next_offset", offset))
        self.follower_served += 1
        self.last_from_follower = True
        return self._deliver(topic, pid, addr, limit, call_async,
                             msgs, offset, next_offset)

    def _deliver(self, topic: str, pid: int, addr: str, limit: int,
                 call_async, msgs: list, offset: int, next_offset: int):
        """Common delivery tail: move the session's position (or arm
        the next `_pf` fetch), run the auto-commit (async when
        prefetching), return the position tuple. With follower reads
        on, `addr` may be the follower that just served — commits
        always re-resolve the LEADER (offset state is a
        quorum-replicated fact only the leader accepts)."""
        commit_addr = addr
        session = False
        if self.client_rack is not None:
            # Commits to the leader always. Rows handed out: the
            # session goes on with the in-rack follower while there is
            # one, else the leader. An empty answer leaves the
            # partition where it was answered: one the follower refused
            # and the leader has nothing for either (the follower has
            # no floor for it yet) waits in the LEADER's fetch for its
            # first rows, instead of going round between the two.
            commit_addr = self._meta.leader_addr(topic, pid) or addr
            if msgs:
                addr = self._rack_addr(self._clock()) or commit_addr
        if self.follower_reads:
            self._pos[(topic, pid)] = int(next_offset)
            commit_addr = self._meta.leader_addr(topic, pid) or addr
        if self._session_on(call_async):
            # Handed out, once: the position moves here and nowhere
            # else (the single-partition path delivers through here
            # too, and hands the partition back to its leader's
            # session).
            part = self._sess.get((topic, pid))
            if part is None:
                part = self._sess[(topic, pid)] = _Part(
                    limit, addr, self._clock(), self._asked)
            part.answer, part.pos, part.addr = None, int(next_offset), addr
            session = True
        elif self.prefetch > 0 and call_async is not None:
            # Re-arm at next_offset. After an EMPTY window only a
            # long-polling fetch is worth keeping in flight (a plain one
            # would answer empty again immediately; drains break on
            # empty anyway).
            if msgs or self.long_poll_s > 0:
                wait_s = self.long_poll_s if not msgs else 0.0
                req = {"type": "consume", "topic": topic, "partition": pid,
                       "consumer": self.consumer_id, "max_messages": limit,
                       "offset": int(next_offset)}
                if wait_s > 0:
                    req["wait_s"] = wait_s
                fetch_addr = commit_addr
                # Route the readahead to a leased follower only on
                # backlog evidence (a FULL window just came back) and
                # never for a long-poll park — tail reads sit above the
                # follower's floor by definition and would only bounce.
                if (self.follower_reads and wait_s == 0.0
                        and len(msgs) >= limit):
                    fa = self._meta.follower_addr()
                    if fa is not None:
                        fetch_addr = fa
                        req["follower_ok"] = True
                try:
                    fut = call_async(fetch_addr, req)
                    self._pf[(topic, pid)] = {
                        "offset": int(next_offset), "fut": fut,
                        "addr": fetch_addr, "limit": limit, "wait_s": wait_s,
                    }
                except RpcError:
                    pass  # connection hiccup: next call goes sync
        if msgs and self.auto_commit:
            if self.prefetch > 0 and call_async is not None:
                c = self._commits.setdefault(commit_addr, _LeaderCommits())
                c.owed[(topic, pid)] = max(int(next_offset),
                                           c.owed.get((topic, pid), 0))
                self._drive_commits(commit_addr, call_async)
            else:
                # strict: ack before deliver
                self.commit(topic, pid, next_offset)
        if session and self.long_poll_s > 0:
            # Handed out: the leader's next parked fetch can list this
            # partition at its new position.
            self._arm(addr, self._clock(), call_async)
        return msgs, pid, offset, next_offset

    # ------------------------------------------------------------- commits

    def _commit_req(self, offsets: dict) -> dict:
        return {"type": "offset.commit.multi", "consumer": self.consumer_id,
                "parts": [{"topic": t, "partition": p, "offset": off}
                          for (t, p), off in offsets.items()]}

    def _drive_commits(self, addr: str, call_async) -> None:
        """Pipelined commits, ONE offset.commit.multi in flight per
        (consumer, leader). The broker runs a connection's requests on a
        worker pool, so two commits of one partition can reach the
        offset table in either order, and the table takes the last
        writer: an older commit overtaking a newer one moved the
        committed position BACK, and the next fetch without an explicit
        offset re-delivered what lay between (seen on the chip at 130k
        msgs/s, PR 28: a commit took longer than the poll interval, 512
        messages came twice). So while one request is in flight the
        newest offsets are PARKED behind it — per partition they only
        grow, a parked one is superseded by the next — and go out, all
        of them in one request, when it has landed. A part that FAILED
        is re-driven synchronously (with retries) before anything newer
        of its partition is sent — errors must not silently drop the
        committed position."""
        c = self._commits.get(addr)
        if c is None:
            return
        if c.fut is not None:
            if not c.fut.done():
                return
            self._land(c, 0)
        if not c.owed:
            return
        sent, c.owed = c.owed, {}
        try:
            c.fut, c.sent = call_async(addr, self._commit_req(sent)), sent
        except RpcError:
            self._redrive(c, sent)  # sync fallback w/ retries

    def _land(self, c: _LeaderCommits, timeout: float) -> None:
        """Take the answer of the request in flight (waiting at most
        `timeout` for it) and re-drive what it did not commit."""
        sent, fut, c.sent, c.fut = c.sent, c.fut, {}, None
        try:
            resp = fut.result(timeout=timeout)
        except Exception:
            resp = {}
        self._redrive(c, sent, resp)

    def _redrive(self, c: _LeaderCommits, sent: dict,
                 resp: Optional[dict] = None) -> None:
        """Commit synchronously, through `commit()`, every offset of
        `sent` that `resp` (an offset.commit.multi's answer) does not
        acknowledge - the newest offset of its partition, parked or
        sent. What is not yet re-driven when `commit()` raises stays
        owed."""
        answers = resp.get("parts") if resp and resp.get("ok") else None
        if not isinstance(answers, list) or len(answers) != len(sent):
            answers = [{}] * len(sent)
        failed = [key for key, ans in zip(sent, answers) if not ans.get("ok")]
        for key in failed:
            c.owed[key] = max(sent[key], c.owed.get(key, 0))
        for key in failed:
            self.commit(key[0], key[1], c.owed[key])
            del c.owed[key]

    def _flush_leader(self, addr: str) -> None:
        """Land everything owed to one leader: wait the request in
        flight out, then send what is parked, synchronously."""
        c = self._commits[addr]
        if c.fut is not None:
            self._land(c, self._timeout)
        if c.owed:
            sent, c.owed = c.owed, {}
            try:
                resp = self._transport.call(addr, self._commit_req(sent),
                                            timeout=self._timeout)
            except RpcError:
                resp = None
            self._redrive(c, sent, resp)

    def _flush_commit_key(self, topic: str, pid: int) -> None:
        key = (topic, pid)
        for addr, c in list(self._commits.items()):
            if key in c.owed or key in c.sent:
                self._flush_leader(addr)

    def flush_commits(self) -> None:
        """Drain every in-flight and parked async auto-commit (prefetch
        mode), re-driving failures through the sync commit path. Called
        by close(); call it directly at consumer-group checkpoints."""
        for addr in list(self._commits):
            self._flush_leader(addr)

    def commit(self, topic: str, partition: int, offset: int) -> None:
        """Commit an absolute offset (replicated through the partition's
        quorum round, like every offset update)."""
        run = self._retry.begin()
        while run.attempt():
            addr = self._meta.leader_addr(topic, partition)
            if addr is None:
                run.note(f"no leader known for {topic}[{partition}]")
                self._refresh_quietly()
                continue
            try:
                resp = self._transport.call(
                    addr,
                    {"type": "offset.commit", "topic": topic,
                     "partition": partition, "consumer": self.consumer_id,
                     "offset": int(offset)},
                    timeout=run.clip(self._timeout),
                )
            except RpcError as e:
                run.note(str(e))
                self._refresh_quietly()
                continue
            if resp.get("ok"):
                return
            err = str(resp.get("error", ""))
            run.note(err)
            if err == "not_leader":
                self._refresh_quietly()
                continue
            if fatal_response_error(err):
                raise ConsumeError(err)
        raise ConsumeError(
            f"offset commit {topic}[{partition}]={offset} failed: "
            f"{run.summary()}"
        )

    def close(self) -> None:
        for f in self._parked.values():
            # An answer that comes now is dropped: it was never handed
            # out, so it moved no position.
            self._abandon(f.fut)
            f.rpc.end()
        self._parked.clear()
        try:
            self.flush_commits()
        except Exception:
            pass  # best-effort: close must not raise over a dead broker
        self._meta.close()
        if self._owns_transport:
            self._transport.close()

    def _refresh_quietly(self) -> None:
        try:
            self._meta.refresh()
        except MetadataError:
            pass
