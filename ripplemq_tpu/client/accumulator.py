"""Record accumulator of the keyed producer: per-partition batches, and
which of them the next produce request to each leader may carry.

Pure bookkeeping: no thread, no transport, no clock of its own (`now` is
an argument), no lock (the owner — `ProducerClient` — calls it under its
own condition). That is what lets the tests drive linger, full-batch
flush, the one-part-per-partition rule and `max_in_flight` on a fake
clock with no sleep.

The rules, in Kafka's words where Kafka has them:

- A message joins the OPEN part of its (topic, partition): the last part
  of that partition's queue, unless it is full (`max_rows()` messages —
  the engine's `max_batch` as the brokers advertise it — or `batch_size`
  bytes), in which case a new part opens behind it.
- A part is READY when it is full, when its oldest message is `linger_s`
  old, when it is a retry whose backoff has passed, or when the producer
  is flushing.
- At most ONE part per partition is in flight (the partition is "muted"
  until that part is acked or failed for good): with the broker's
  (pid, seq) dedup this is what keeps one key's messages in send order
  under retries.
- At most `max_in_flight` requests are outstanding per leader; a request
  carries every ready part of the partitions that leader leads.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Optional


class Part:
    """One partition's batch: what rides a produce.multi request as one
    part and is acked (or refused) on its own."""

    __slots__ = ("topic", "partition", "messages", "khashes", "nbytes",
                 "t_first", "seq", "run", "not_before", "full", "sent",
                 "retrying", "base_offset", "error", "acked_ns", "moved",
                 "traces")

    def __init__(self, topic: str, partition: int, now: float) -> None:
        self.topic = topic
        self.partition = partition
        self.messages: list[bytes] = []
        self.khashes: list[int] = []
        self.nbytes = 0
        self.t_first = now
        self.seq: Optional[int] = None     # reserved when first sent
        self.run = None                    # its RetryRun, from then on
        self.not_before = 0.0              # retry backoff gate
        self.full = False
        self.sent = False                  # left the accumulator once
        self.retrying = False
        self.base_offset: Optional[int] = None
        self.error: Optional[str] = None
        self.acked_ns = 0                  # time.monotonic_ns() at the ack
        # After a reroute: where each message went, [(part, index)].
        self.moved: Optional[list] = None
        self.traces: Optional[list] = None  # sampled messages' open spans

    @property
    def tp(self) -> tuple[str, int]:
        return (self.topic, self.partition)

    @property
    def done(self) -> bool:
        return self.base_offset is not None or self.error is not None


class Accumulator:
    def __init__(self, linger_s: float = 0.001, batch_size: int = 1048576,
                 max_in_flight: int = 5,
                 max_rows: Callable[[], Optional[int]] = lambda: None) -> None:
        if max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1")
        self.linger_s = float(linger_s)
        self.batch_size = int(batch_size)
        self.max_in_flight = int(max_in_flight)
        self._max_rows = max_rows
        # Unsent parts per partition, oldest first; the last one is the
        # open part unless it is full.
        self._queues: dict[tuple[str, int], deque[Part]] = {}
        # Partitions with a part in flight.
        self._muted: set[tuple[str, int]] = set()
        self._in_flight: dict[str, int] = {}
        self.flushing = False

    # ------------------------------------------------------------ append

    def append(self, topic: str, partition: int, message: bytes,
               khash: int, now: float) -> tuple[Part, int, bool]:
        """(part, index of the message in it, whether the sender should
        look again: a part opened or filled)."""
        tp = (topic, partition)
        q = self._queues.get(tp)
        if q is None:
            q = self._queues[tp] = deque()
        part = q[-1] if q else None
        opened = part is None or part.full or part.retrying
        if opened:
            part = Part(topic, partition, now)
            q.append(part)
        idx = len(part.messages)
        part.messages.append(message)
        part.khashes.append(khash)
        part.nbytes += len(message)
        rows = self._max_rows()
        if part.nbytes >= self.batch_size or (
                rows is not None and idx + 1 >= rows):
            part.full = True
        return part, idx, opened or part.full

    def pending(self) -> int:
        """Parts not yet acked or failed: queued and in flight."""
        return sum(len(q) for q in self._queues.values()) + len(self._muted)

    # ------------------------------------------------------------- drain

    def _ready_at(self, part: Part) -> float:
        if part.retrying:
            return part.not_before
        if part.full or self.flushing:
            return 0.0
        return part.t_first + self.linger_s

    def drain(self, now: float, leader_of: Callable[[str, int], Optional[str]]
              ) -> tuple[dict[str, list[Part]], Optional[float], bool]:
        """Take what may go out now: ({leader address: parts of ONE
        request}, when to look again if nothing else happens — None:
        only a response or an append can change anything —, whether some
        ready part has no known leader). The parts returned are in
        flight from here on: their partitions are muted and their
        leaders' request counts raised."""
        out: dict[str, list[Part]] = {}
        wake: Optional[float] = None
        lost = False
        for tp, q in self._queues.items():
            if not q or tp in self._muted:
                continue
            part = q[0]
            at = self._ready_at(part)
            if at > now:
                wake = at if wake is None else min(wake, at)
                continue
            addr = leader_of(part.topic, part.partition)
            if addr is None:
                lost = True
                continue
            if addr not in out and \
                    self._in_flight.get(addr, 0) >= self.max_in_flight:
                continue  # a response from that leader will wake us
            out.setdefault(addr, []).append(part)
        for addr, parts in out.items():
            self._in_flight[addr] = self._in_flight.get(addr, 0) + 1
            for part in parts:
                self._queues[part.tp].popleft()
                self._muted.add(part.tp)
                part.sent = True
        return out, wake, lost

    def saturated(self) -> bool:
        """Every leader this producer is talking to has `max_in_flight`
        requests out: nothing can be sent before a response comes, so
        the sender need not look (a leader never contacted yet waits at
        most one response time for its first request)."""
        return bool(self._in_flight) and all(
            n >= self.max_in_flight for n in self._in_flight.values())

    # ---------------------------------------------------------- outcomes

    def request_done(self, addr: str) -> None:
        n = self._in_flight.get(addr, 0) - 1
        if n > 0:
            self._in_flight[addr] = n
        else:
            self._in_flight.pop(addr, None)

    def complete(self, part: Part) -> None:
        """The part was acked or failed for good: its partition's next
        part may go."""
        self._muted.discard(part.tp)
        if not self._queues.get(part.tp):
            self._queues.pop(part.tp, None)

    def retry(self, part: Part, not_before: float) -> None:
        """Back to the HEAD of its partition's queue, under the identity
        it already has: nothing of that partition passes it."""
        part.retrying = True
        part.not_before = not_before
        self._muted.discard(part.tp)
        self._queues.setdefault(part.tp, deque()).appendleft(part)

    def take_partition(self, part: Part) -> list[Part]:
        """A reroute: the refused part and everything still queued behind
        it, oldest first; the partition is left empty and unmuted."""
        rest = self._queues.pop(part.tp, None) or ()
        self._muted.discard(part.tp)
        return [part, *rest]

    def requeue_front(self, parts: list[Part]) -> None:
        """Rerouted parts go AHEAD of what their new partitions already
        hold (they are older)."""
        for part in reversed(parts):
            self._queues.setdefault(part.tp, deque()).appendleft(part)
