"""BrokerServer: one broker process — dispatch, duties, engine access.

The reference broker stacks five RpcProcessors on one Bolt server plus two
tiers of JRaft (reference: mq-broker/.../TopicsRaftServer.java:106-120,
BrokerServer.java). The equivalent surface here, one dict-typed request
each (wire/transport dispatches by the "type" field):

  meta.topics      ← TopicsRequestProcessor (read path; served by ANY broker)
  meta.propose     ← TopicsRequestProcessor write + PartitionLeaderUpdate
                     forwarding (both were metadata Raft writes)
  produce          ← MessageAppendRequestProcessor
  produce.multi    ← (no equivalent) a keyed producer's request for
                     many partitions of this leader, answered per part
  consume          ← MessageBatchReadRequestProcessor
  consume.multi    ← (no equivalent) a readahead consumer's fetch for
                     many partitions of this leader, answered per part
  offset.commit    ← ConsumerOffsetUpdateRequestProcessor
  offset.commit.multi ← (no equivalent) that consumer's commit, likewise
  raft.*           ← JRaft's internal traffic (here: hostraft, metadata only)
  engine.*         ← controller-only: data-plane access for peer brokers
                     (the reference needs no equivalent — every JVM broker
                     holds state; here the device mesh is driven by the
                     CURRENT controller and peers reach it by RPC)
  repl.rounds      ← standby side of committed-round replication: the
                     controller streams every persisted round to the
                     metadata-replicated standby set, any member of which
                     can be promoted on controller death — restoring the
                     any-broker fault tolerance the reference gets from
                     per-broker JRaft groups (PartitionRaftServer.java:83-93;
                     see broker/replication.py)

Leader checks REFUSE with a hint instead of the reference's
missing-return fallthrough (MessageAppendRequestProcessor.java:29-33 — a
non-leader broker there answers "Not leader" and then appends anyway;
documented deviation, SURVEY.md §7 faithfulness checklist).

Broker duties, each a small periodic loop:
- metadata-leader duty: liveness-driven assignment refresh (the 10 s
  membership monitor of TopicsRaftServer.java:202-217).
- controller duty: batched device elections for leaderless partitions +
  lag repair resync (host-coordinated election, SURVEY.md §7 layer 5).
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import Future
from typing import Callable, Optional


from ripplemq_tpu.broker.dataplane import DataPlane, NotCommittedError
from ripplemq_tpu.obs.lockwitness import make_lock
from ripplemq_tpu.obs.spans import (
    NULL_SPAN,
    TraceContext,
    ctx_from_wire,
    derive_trace_id,
    sampled,
)
from ripplemq_tpu.broker.hostraft import LEADER, RAFT_TYPES, RaftNode, RaftRunner
from ripplemq_tpu.broker.manager import (
    OP_BATCH,
    OP_CONSUMER_SLOT_CLEAN,
    OP_GROUP_DELETE,
    OP_GROUP_JOIN,
    OP_GROUP_LEAVE,
    OP_MERGE_PARTITIONS,
    OP_REGISTER_CONSUMER,
    OP_REGISTER_PRODUCER,
    OP_RETIRE_PRODUCER,
    OP_SET_FOLLOWER_LEASES,
    OP_SET_STANDBYS,
    OP_SPLIT_CUTOVER,
    OP_SPLIT_PARTITION,
    ConsumerTableFullError,
    PartitionManager,
)
from ripplemq_tpu.groups.coordinator import GroupLiveness
from ripplemq_tpu.groups.state import group_consumer_name
from ripplemq_tpu.metadata.cluster_config import ClusterConfig
from ripplemq_tpu.metadata.models import group_key, topics_to_wire
from ripplemq_tpu.utils.logs import get_logger
from ripplemq_tpu.wire.retry import RetryPolicy
from ripplemq_tpu.wire.transport import (
    InProcNetwork,
    RpcError,
    TcpClient,
    TcpServer,
    Transport,
)

log = get_logger("broker")


_RESOLVE = object()  # "look the local plane up yourself" (_quorum_refusal)

# Control-plane wave batching (_batch_duty): the membership/pid commands
# a broker receives ride ONE OP_BATCH proposal per window, or as soon as
# _META_BATCH_MAX are queued (bounds the proposal's payload and what a
# full queue adds to its oldest waiter's latency).
_META_BATCH_S = 0.05
_META_BATCH_MAX = 256
# Heartbeat relay (_beats_relay_duty): ONE group.beats frame per interval
# carries a broker's buffered member beats to the metadata leader: this
# long, or a quarter of group_session_timeout_s where that is shorter
# (a beat relayed late in a short session keeps nobody alive).
_HEARTBEAT_RELAY_S = 0.5


class _UpstreamRefusal(Exception):
    """A typed refusal from the controller that must reach the client
    VERBATIM (e.g. `unavailable:` quorum-lost degradation) — wrapping it
    in not_committed/internal would strip the prefix the error taxonomy
    and operator tooling key on. Carries the upstream response dict."""

    def __init__(self, resp: dict) -> None:
        super().__init__(str(resp.get("error", "")))
        self.resp = dict(resp)


class _AppendBatch:
    """The appends of one produce.multi: gathered while its parts are
    admitted, then submitted TOGETHER — to the local plane under one hold
    of its lock (`DataPlane.submit_appends`), or, on a leader that is not
    the controller, as ONE engine.append_multi frame — and answered item
    by item."""

    def __init__(self, timeout_s: float) -> None:
        self.items: list[tuple] = []    # (slot, messages, pid, seq, tctx)
        self.results: list = []         # future | base offset | exception
        self.timeout_s = timeout_s

    def add(self, slot: int, messages: list, pid: int, seq: int,
            tctx=None) -> Callable[[], int]:
        i = len(self.items)
        self.items.append((slot, messages, pid, seq, tctx))

        def wait() -> int:
            r = self.results[i]
            if isinstance(r, Future):
                return int(r.result(timeout=self.timeout_s))
            if isinstance(r, Exception):
                raise r
            return r

        return wait


class _BarrierGate:
    """Batched read-index barrier (SURVEY.md §7 "read semantics", the
    read-index option). Callers block until a barrier that STARTED after
    their arrival completes; concurrent callers share one barrier, so
    the per-read cost under load is a fraction of one standby round
    trip. `fire` confirms leadership — here, an empty epoch-fenced
    record batch through the standby ack stream (a standby knowing a
    newer epoch rejects it, a partitioned standby times it out; either
    way the read REFUSES instead of serving a possibly-stale prefix)."""

    def __init__(self, fire) -> None:
        self._fire = fire
        self._lock = make_lock("_BarrierGate._lock")
        self._pending = None  # Future whose fire has NOT started yet

    def wait(self, timeout_s: float) -> None:
        from concurrent.futures import Future
        from concurrent.futures import TimeoutError as FuturesTimeoutError

        with self._lock:
            fut = self._pending
            if fut is None:
                fut = self._pending = Future()
                threading.Thread(
                    target=self._run, args=(fut,), daemon=True,
                    name="read-barrier",
                ).start()
        try:
            fut.result(timeout=timeout_s)
        # Both classes: the gate can hit a result-wait timeout, or the
        # fire thread can set a FuturesTimeoutError raised by a standby
        # ack wait — pre-3.11 neither is the builtin TimeoutError.
        except (TimeoutError, FuturesTimeoutError):
            raise NotCommittedError(
                "read barrier timed out: leadership unconfirmed"
            ) from None

    def _run(self, fut) -> None:
        # Leave _pending BEFORE firing: a caller arriving after the fire
        # began must wait for the NEXT barrier (its leadership proof
        # must postdate the read's arrival).
        with self._lock:
            if self._pending is fut:
                self._pending = None
        try:
            self._fire()
            fut.set_result(True)
        except Exception as e:
            fut.set_exception(e)


class _ReplStreamGate:
    """Per-(sender, epoch) IN-ORDER application gate for the pipelined
    replication stream (broker/replication.py _Sender): the sender
    keeps `repl_pipeline_depth` frames in flight, each stamped with a
    per-stream sequence number, and concurrent RPC worker threads may
    decode them out of order — this gate serializes APPLICATION to
    sequence order without giving up the pipelining (successors park
    briefly instead of bouncing). `enter` returns "apply" for the
    in-order frame and for any DUPLICATE (sseq below expected: a
    rewound sender re-sends frames whose first delivery may already
    have applied — re-application is harmless, replay is
    later-record-wins), or "gap" when predecessors never arrive inside
    the wait (wire loss): the handler refuses with `repl_seq_gap` +
    the expected counter and the sender rewinds onto it — which also
    re-syncs a RESTARTED standby whose gate restarted at zero."""

    def __init__(self) -> None:
        self._lock = make_lock("_ReplStreamGate._lock")
        self._cond = threading.Condition(self._lock)
        self._expected: dict[tuple, int] = {}

    def expected(self, key: tuple) -> int:
        with self._cond:
            return self._expected.get(key, 0)

    def enter(self, key: tuple, sseq: int, timeout_s: float = 1.0) -> bool:
        """Block until `sseq` is applicable; False = sequence gap."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            if key not in self._expected:
                # New (sender, epoch) stream: retire the sender's older
                # epochs (the dict must not grow with failovers).
                for k in [k for k in self._expected
                          if k[0] == key[0] and k[1] < key[1]]:
                    del self._expected[k]
                self._expected[key] = 0
            while True:
                # .get, not []: a newer-epoch frame for the same sender
                # retires this key while we park — the woken thread
                # must answer "gap" (the sender's old-epoch rewind hits
                # the stale_epoch fence anyway), not KeyError out of
                # the handler.
                cur = self._expected.get(key)
                if cur is None:
                    return False
                if sseq <= cur:
                    return True
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(timeout=remaining)

    def applied(self, key: tuple, sseq: int) -> None:
        """Mark `sseq` durably applied; wakes parked successors. Only
        called on success — a failed apply leaves `expected` in place
        so the sender's rewind re-delivers."""
        with self._cond:
            cur = self._expected.get(key)
            # A retired (newer epoch arrived mid-apply) stream must not
            # be resurrected here — the entry would leak until the next
            # same-sender retirement.
            if cur is not None and sseq + 1 > cur:
                self._expected[key] = sseq + 1
            self._cond.notify_all()


class _WaveWaiter:
    """One enqueued control-plane command's handle: the RPC handler
    parks on `event` until the wave carrying the command is PROPOSED
    (`ok` = the propose outcome) — commitment is still observed by the
    handler's own local-apply poll, exactly as on the unbatched path."""

    __slots__ = ("event", "ok")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.ok = False


class BrokerServer:
    """One broker. `net` is an InProcNetwork for single-process clusters
    (tests, single-chip deployments) or None for real TCP sockets."""

    def __init__(
        self,
        broker_id: int,
        config: ClusterConfig,
        net: Optional[InProcNetwork] = None,
        dataplane: Optional[DataPlane] = None,
        engine_mode: str = "local",
        tick_interval_s: float = 0.05,
        duty_interval_s: float = 0.1,
        data_dir: Optional[str] = None,
        engine_workers: Optional[list[str]] = None,
    ) -> None:
        # FIRST: a partially-constructed broker (any raise below) must
        # refuse teardown — harness/cluster cleanup calls stop() on
        # whatever exists, and running it against half-constructed state
        # turns one boot failure into a cascade (advisor round-5
        # finding). Flipped to False as __init__'s last statement.
        self._stopped = True
        self.broker_id = broker_id
        self.config = config
        self.info = config.broker(broker_id)
        if config.lock_witness:
            # Debug lock witness (obs/lockwitness.py): enabled BEFORE
            # any lock below is constructed, so every host-path mutex
            # this broker creates records acquisition orderings.
            # Process-global by design — an in-proc cluster's brokers
            # share one witnessed graph, which is what the chaos
            # cross-check wants.
            from ripplemq_tpu.obs import lockwitness

            lockwitness.enable()
        # --- telemetry plane (obs/): one metrics registry + one flight-
        # recorder ring per broker, created FIRST so every layer below
        # (store, replicator, data plane) threads through the same pair.
        # config.obs=False swaps in no-op metrics (the A/B knob) and
        # silences the process-global codec frame stats; the flight
        # recorder stays on (see obs/trace.py).
        from ripplemq_tpu.obs.metrics import Metrics
        from ripplemq_tpu.obs.trace import FlightRecorder
        from ripplemq_tpu.wire import codec as _codec

        # A traced broker (trace_sample_n > 0, which requires obs) also
        # names its waits: the registry reads a stage's CPU beside its
        # wall (obs/stages.py), the three locks of
        # lockwitness.TIMED_LOCKS built from here on are timed by role
        # onto this registry (enabled BEFORE any of them is
        # constructed, like the witness above), and a wake-up probe
        # says how long a runnable thread waits for the interpreter
        # (obs/wakeprobe.py). Untraced, none of the three exists.
        self._waits = config.trace_sample_n > 0
        self.metrics = Metrics(enabled=config.obs, waits=self._waits)
        self.recorder = FlightRecorder()
        self._wake_probe = None
        if self._waits:
            from ripplemq_tpu.obs.wakeprobe import WakeProbe

            self._time_locks()
            self._wake_probe = WakeProbe(self.metrics, self.recorder)
        # Causal tracing plane (obs/spans.py): one span ring per broker
        # process, serving admin.spans. None when trace_sample_n=0 —
        # every emit site below gates on `self.spans is not None` (or
        # on a None ctx), so the untraced hot path never reads a clock.
        # The ring shares the metrics clock so the engine's round-stage
        # timestamps can be recorded as spans verbatim (same monotonic
        # domain; trace_sample_n > 0 requires obs=True at parse time).
        from ripplemq_tpu.obs.spans import SpanRing
        self.spans = (
            SpanRing(f"broker{broker_id}",
                     clock=self.metrics.clock, metrics=self.metrics)
            if config.trace_sample_n > 0 else None
        )
        # Produce-ack latency as the CLIENT of this broker experiences
        # it (admission → all pipelined rounds settled), observed in
        # _handle_produce. This is the SLO controller's plant output:
        # the p99 it steers toward slo_p99_ack_ms.
        self._m_ack_us = self.metrics.histogram("produce.ack_us")
        # produce.multi (a keyed producer's request for many
        # partitions): requests, the parts they carried, and handler
        # start to the last part's submit_append - the Python cost of
        # admitting a request, before any wait for a round.
        self._m_multi_requests = self.metrics.counter(
            "produce.multi_requests")
        self._m_multi_parts = self.metrics.counter("produce.multi_parts")
        self._m_multi_admit_us = self.metrics.histogram(
            "produce.multi_admit_us")
        # Consume-ack latency, same contract on the read side (observed
        # in _handle_consume around the whole answer — leader, follower,
        # and refusal paths alike): the p99 the SLO controller's consume
        # twin steers toward slo_p99_consume_ms via read_coalesce_s.
        self._m_consume_ack_us = self.metrics.histogram("consume.ack_us")
        # consume.multi / offset.commit.multi (a readahead consumer's
        # session with this leader): requests and the parts they
        # carried - parts a request says how widely sessions engage.
        self._m_cmulti_requests = self.metrics.counter(
            "consume.multi_requests")
        self._m_cmulti_parts = self.metrics.counter("consume.multi_parts")
        # Long-polling fetches (`_fetch`), counted by the broker that
        # took the client's request: requests that parked, how the park
        # ended, and the consume / consume.multi requests whose reply
        # held a row (parked or not). fetch.wake_late_us is the plane's
        # side: the settle release that ended a park to its rows in hand.
        self._m_fetch_parked = self.metrics.counter("fetch.parked")
        self._m_fetch_expired = self.metrics.counter("fetch.expired")
        self._m_fetch_woken = self.metrics.counter("fetch.woken")
        self._m_fetch_answered = self.metrics.counter("fetch.answered")
        self._m_wake_late_us = self.metrics.histogram("fetch.wake_late_us")
        # Seconds the request a thread is serving stood parked, so that
        # consume.ack_us times the handler's work and not the stand.
        self._parked_tls = threading.local()
        self._m_omulti_requests = self.metrics.counter(
            "commit.multi_requests")
        self._m_omulti_parts = self.metrics.counter("commit.multi_parts")
        # One sealed segment's shard pushed to its peer (_shard_duty):
        # file read, frame encode, the RPC and its answer — on the duty
        # thread, so histogram only.
        self._st_shard_put = self.metrics.stage("seal.shard_put",
                                                annotate=False)
        # Codec stats are process-global: set them symmetrically (last
        # constructed broker wins) rather than latching off forever —
        # a one-way disable would freeze the A/B's obs=True arm when an
        # obs=False broker ran earlier in the same process.
        _codec.enable_stats(config.obs)
        self._net = net
        self._engine_mode = engine_mode
        # Multi-host spmd: engine-worker endpoints on the OTHER hosts of
        # the jax.distributed mesh (parallel.worker); the controller's
        # DataPlane broadcasts its engine-call stream to them.
        self._engine_workers = list(engine_workers or [])
        self._duty_interval_s = duty_interval_s
        self._stop = threading.Event()
        self._started = False
        self.data_dir = data_dir

        # --- transports (before the store: boot-time shard refill calls
        # out to live peers) ---
        if net is not None:
            self.client: Transport = net.client(self.addr)
            # Same source address (fault injection must treat raft
            # traffic exactly like data traffic), distinct client object.
            self._raft_client: Transport = net.client(self.addr)
            self._tcp_server = None
        else:
            self.client = TcpClient()
            # The metadata plane gets its OWN connections: raft appends
            # and meta proposals must not queue behind megabyte
            # replication/engine frames on the shared pipelined sockets
            # (head-of-line blocking there stalls commits for seconds
            # under produce load — elections, standby joins, and
            # failover all ride these messages).
            self._raft_client = TcpClient()
            self._tcp_server = TcpServer(
                self.info.host, self.info.port, self.dispatch,
                workers=config.rpc_workers,
                metrics=self.metrics,
            )

        # --- committed-round store ---
        # EVERY broker holds one, so any broker can serve as a replication
        # standby and take over as controller (broker/replication.py).
        # Disk-backed under data_dir (the role JRaft's storage URIs play
        # for the reference, TopicsRaftServer.java:134-136 — which the
        # reference only half-uses: its FSMs never snapshot, SURVEY.md §5);
        # in-memory otherwise (matching the reference's own durability for
        # partition data: process memory + replication,
        # PartitionStateMachine.java:26-27).
        self._store_dir = None
        self._peer_shard_dir = None
        self._owns_store = dataplane is None
        self._pushed_shards: set[str] = set()
        self._bad_shard_targets: set[int] = set()
        self._pending_shard_drops: list[tuple[int, str]] = []
        self._shard_push_seeded = False
        self._last_shard_push = 0.0
        self._store_quarantined = False
        # How many striped-promotion rebuilds this process ran
        # (admin.stats `stripe_rebuilds`; stripes/recovery.py).
        self._stripe_rebuilds = 0
        # SLO shed machine's empty-standby-set latch (see _slo_degraded:
        # the signal arms only after a standby ever joined — genesis
        # settles member-less by design). Written from the slo control
        # thread only.
        self._slo_had_standbys = False
        # Since the last quarantine, has this broker been observed OUT of
        # the replicated standby set? A broker that died IN the set boots
        # with stale membership still naming it — which proves nothing
        # about its (now emptied) store. Only an out-then-in transition
        # means the controller re-ran the full catch-up stream before
        # re-proposing membership (see _takeover_duty / _handle_repl_rounds).
        self._quarantine_left_set = False
        if dataplane is not None:
            self._round_store = dataplane.store  # may be None
        elif data_dir is not None:
            import os

            from ripplemq_tpu.storage.erasure import repair_store
            from ripplemq_tpu.storage.segment import SegmentStore

            self._store_dir = os.path.join(data_dir, "segments")
            self._peer_shard_dir = os.path.join(data_dir, "rs_peer")
            # Disaster path first: sealed segments whose file AND local
            # shards are gone refill their rs/ sets from peer-held shard
            # copies (best-effort — unreachable peers just skip), then
            # the ordinary local heal rebuilds any missing/corrupt sealed
            # segment from any 3 of its 5 RS shards — all BEFORE opening
            # for append (the open creates a fresh active segment whose
            # index must come after every recovered one). Damage that
            # survives BOTH passes (a flipped record in the active
            # segment, a lost sealed segment with no shard set) is
            # quarantined: the broker reopens empty and re-replicates
            # through standby catch-up instead of crash-looping at its
            # next promotion or serving a CRC-failing row
            # (_validate_or_quarantine_store).
            self._refill_shards_from_peers()
            repair_errors: list[str] = []
            repair_store(self._store_dir, errors=repair_errors)
            self._validate_or_quarantine_store()
            self._round_store = SegmentStore(
                self._store_dir, erasure=True,
                segment_bytes=config.segment_bytes,
                retention_bytes=config.store_retention_bytes,
                metrics=self.metrics,
            )
            # Boot-time shard re-encode failures surface beside the
            # background encoder's, in admin.stats `erasure_errors`.
            self._round_store.erasure_errors.extend(repair_errors)
        else:
            from ripplemq_tpu.storage.memstore import MemoryRoundStore

            self._round_store = MemoryRoundStore()
        self._repl_last_flush = 0.0

        # --- control plane (the dataplane attaches after, since the
        # restored metadata decides who the controller is) ---
        self.manager = PartitionManager(broker_id, config, None)
        # Group lifecycle events (join/leave/eviction/generation bumps)
        # land in THIS broker's flight recorder — the rebalance timeline
        # chaos verdicts merge.
        self.manager.recorder = self.recorder
        # Volatile heartbeat ledger (consulted only while this broker is
        # the metadata leader — see _group_duty), plus the empty-group
        # retention stamps (group → first seen empty on THIS leader; a
        # leader change restarts every window, the same volatile-grace
        # rule as member sessions).
        self._group_liveness = GroupLiveness()
        self._group_empty_since: dict[str, float] = {}
        # --- control-plane wave batching (_batch_duty) ---
        # Membership/pid commands received by THIS broker queue here and
        # ride ONE OP_BATCH proposal per wave (_META_BATCH_S cadence, or
        # early at _META_BATCH_MAX) instead of one raft proposal each.
        # Each entry carries the waiter its RPC handler blocks on until
        # the wave is proposed. Both locks are leaves: never held across
        # a propose/RPC, so they stay out of every existing lock order.
        self._intake_lock = make_lock("BrokerServer._intake_lock")
        self._intake: list[tuple[dict, _WaveWaiter]] = []
        # Serializes wave formation: waves must reach the metadata
        # leader in FIFO intake order (an enqueue that hits
        # _META_BATCH_MAX drains inline, racing the duty tick).
        self._intake_drain_lock = make_lock(
            "BrokerServer._intake_drain_lock"
        )
        self._last_wave = 0.0
        self._wave_count = 0       # waves proposed (OP_BATCH commands)
        self._wave_events = 0      # sub-commands carried by those waves
        self._wave_failures = 0    # waves whose propose ultimately failed
        self._wave_size_hist: dict[str, int] = {}  # pow2 bucket → waves
        # --- heartbeat relay plane (_beats_relay_duty) ---
        # Member heartbeats are ANSWERED locally from the replicated
        # group view and the per-member stamps buffered here; one
        # group.beats frame per relay interval carries them to the
        # metadata leader's liveness ledger — leader heartbeat RPC load
        # is O(brokers), not O(members).
        self._beat_lock = make_lock("BrokerServer._beat_lock")
        self._beat_buffer: set[tuple[str, str]] = set()
        self._beats_relayed = 0    # stamps this LEADER ingested from frames
        self._beat_frames = 0      # frames this broker delivered
        self._heartbeats_local = 0  # member beats answered locally
        self._last_beat_relay = 0.0
        # Producer-id expiry (metadata-leader duty): volatile ledger
        # name → (seen counter, first observed at) — the same per-
        # tenure grace rule as group liveness: cleared on losing the
        # lease, so a re-elected leader grants every pid a full
        # retention window instead of reaping off a previous tenure's
        # stamps. The replicated half is the seen counter itself
        # (bumped by every re-registration; the reap apply re-checks
        # it, manager._apply_retire_producer).
        self._pid_seen_at: dict[str, tuple[int, float]] = {}
        self._last_pid_reconcile = 0.0
        # Broker-stamped idempotence for pid-LESS produces: the leader
        # stamps each forwarded batch with its own metadata-issued pid +
        # a per-slot sequence, so a duplicated leader→controller
        # engine.append RPC (the wire's at-least-once window) collapses
        # in the controller's dedup table even for clients that never
        # opted into idempotence. Registered via the duty loop; until
        # the pid applies, produces flow unstamped (at-least-once, the
        # pre-PR behavior).
        import uuid as _uuid

        self._broker_pid: Optional[int] = None
        # Per-boot nonce: a restart must never reuse a pid whose
        # sequence counters it lost.
        self._pid_nonce = _uuid.uuid4().hex[:12]
        self._broker_pid_name = (
            f"_broker/{broker_id}/{self._pid_nonce}"
        )
        self._broker_pid_proposed = 0.0
        self._broker_pid_refreshed = 0.0
        self._stamp_lock = make_lock("BrokerServer._stamp_lock")
        self._stamp_seqs: dict[int, int] = {}
        # Pipelined replication stream gate (see _ReplStreamGate): the
        # standby side of repl.rounds applies frames in per-stream
        # sequence order while the sender keeps a window in flight.
        self._repl_gate = _ReplStreamGate()
        # --- follower read plane (broker/follower.py) ---
        # Serve consumes from the bytes replication already shipped
        # here: a floor-fenced row cache fed by the repl handlers
        # (full-copy records + piggybacked floors, or own-stripe frames
        # decoded on read). Gated per ANSWER on the metadata-plane
        # lease (manager.follower_lease) — construction is cheap and
        # unconditional on the knob so ingest starts before the first
        # lease grant lands.
        self.follower_plane = None
        self._follower_cursors: dict[int, list] = {}
        if config.follower_reads:
            self._make_follower_plane()
        persist_fn = None
        if data_dir is not None:
            import os

            from ripplemq_tpu.storage.metastore import MetaStore

            self._metastore = MetaStore(os.path.join(data_dir, "meta.bin"))
            persist_fn = self._metastore.save
        else:
            self._metastore = None
        # Metadata election timeout → hostraft tick counts (randomized in
        # [1x, 2x], Raft-style; the reference's JRaft equivalent is
        # NodeOptions.setElectionTimeoutMs, TopicsRaftServer.java:131).
        etick = max(2, int(round(config.metadata_election_timeout_s
                                 / tick_interval_s)))
        # Controllership-claim provenance (consumed by _takeover_duty):
        # an OP_SET_CONTROLLER that applies at a raft index BEYOND the
        # restored log's end is a live promotion this process witnessed;
        # a claim held without one is recovered (or genesis-config)
        # state. The distinction matters because a restarted
        # controller's own store may have silently lost its acked tail
        # (torn-tail trim is a legitimate crash repair), while a live
        # promotion's store was acked complete by construction.
        self._recovered_raft_end = 0
        self._promoted_live = False
        node = RaftNode(
            broker_id,
            config.broker_ids(),
            apply_fn=self._apply_committed,
            snapshot_fn=self.manager.snapshot,
            restore_fn=self.manager.restore,
            election_ticks=(etick, 2 * etick),
            seed=broker_id * 7919,
            compact_threshold=256,
            persist_fn=persist_fn,
        )
        if self._metastore is not None:
            saved = self._metastore.load()
            if saved is not None:
                node.restore(saved)
                self._recovered_raft_end = node.last_index()
        self.runner = RaftRunner(
            node,
            self._raft_client,
            addr_of=self._addr_of,
            tick_interval_s=tick_interval_s,
            rpc_timeout_s=min(1.0, config.rpc_timeout_s),
        )
        # Liveness horizon in ticks ≈ metadata election timeout.
        self._alive_horizon = max(
            4, int(config.metadata_election_timeout_s / tick_interval_s)
        )

        # --- engine (the CURRENT controller owns the device program;
        # controllership is replicated metadata and moves on failover) ---
        self.dataplane: Optional[DataPlane] = None
        self._owns_dataplane = False
        self._replicator = None
        self._warm_thread: Optional[threading.Thread] = None
        self._catchup_thread: Optional[threading.Thread] = None
        self._boot_failures = 0     # consecutive data-plane boot failures
        if dataplane is not None:
            self.dataplane = dataplane
            self.manager.attach_dataplane(dataplane)
            if dataplane.replicate_fn is None and self._round_store is not None:
                self._wire_replicator(dataplane)
        # No construction-time boot when this broker's (possibly
        # RECOVERED) metadata names it controller: recovered metadata can
        # be arbitrarily stale — a broker restarting after a controller
        # failover would resurrect a deposed plane and serve stale reads
        # (and, with an empty persisted standby set, even ACK produces
        # with no fencing proof) until its raft caught up — the
        # split-brain window the seeded chaos soak caught as acked-loss
        # and offset-regression violations. The takeover duty boots the
        # plane instead, gated on _metadata_current(): genesis cold
        # start costs one metadata election (~the existing bootstrap
        # fixpoint); restart-into-a-moved-on-cluster never boots at all.

        self._duty_thread = threading.Thread(
            target=self._duty_loop, daemon=True, name=f"broker-duty-{broker_id}"
        )
        # Ring of recent duty failures. Mutated (append + del-slice
        # trim) from the duty loop AND catch-up threads — the pair of
        # list ops must not interleave across threads (ownership lint,
        # PR 11), so every mutation rides _errors_lock; snapshot reads
        # (admin.stats list()) stay bare.
        self._errors_lock = make_lock("BrokerServer._errors_lock")
        self.duty_errors: list[str] = []
        # Membership-poll cadence (reference: the 10 s membership monitor,
        # TopicsRaftServer.java:216): assignment/controller planning runs
        # at most every membership_poll_s, first pass immediate.
        self._last_membership_poll = 0.0
        # Follower-lease grant debounce (_follower_lease_duty).
        self._last_lease_grant = 0.0
        # Elastic-partition reconfiguration (split/merge) surface:
        # dual-write forwards this broker served as a handoff leader,
        # generation-fence refusals it answered (both land in the
        # admin.stats `reconfig` block), and the reconfig duty's LOCAL
        # first-seen clock per open handoff window — the
        # split_handoff_timeout_s bound is a duty deadline, not
        # replicated state: a controller failover restarts the clock,
        # which delays the cutover but never loses it.
        self._forwarded_writes = 0
        self._gen_fence_refusals = 0
        self._handoff_seen: dict = {}
        # Auto-split heat ranking: (topic, pid) → committed log end at
        # the previous duty pass (duty thread only).
        self._autosplit_prev_ends: dict = {}
        # Repair-scan cadence (see _controller_duty): lag repair needs a
        # device fetch, so it must not ride every duty tick.
        self._last_repair_scan = 0.0
        self._engine_busy_at = 0.0  # last duty tick the plane looked busy
        # Read-index barrier (linearizable_reads; see _BarrierGate).
        self._barrier_gate = _BarrierGate(self._fire_read_barrier)
        # --- SLO autopilot (ripplemq_tpu/slo/) ---
        # Always constructed (admission quotas work without the loop;
        # admin.stats serves the `slo` block either way); the control
        # thread only starts when slo_p99_ack_ms > 0. dataplane_fn
        # resolves lazily to the CURRENT controller's plane — knob
        # adjustment and engine-side shed signals follow controllership
        # the same way engine RPCs do.
        from ripplemq_tpu.slo.controller import SloController

        self.slo = SloController(
            config, metrics=self.metrics, recorder=self.recorder,
            dataplane_fn=self._local_engine,
            degraded_fn=self._slo_degraded,
        )
        # Fully constructed: teardown may now run (see the top of __init__).
        self._stopped = False

    # ------------------------------------------------------------ lifecycle

    @property
    def addr(self) -> str:
        return self.info.address

    @property
    def is_controller(self) -> bool:
        """Whether this broker currently drives the device program (a
        replicated, epoch-fenced metadata fact — not the static config
        role it was before controller failover existed)."""
        return self.manager.current_controller() == self.broker_id

    def _boot_dataplane(self) -> None:
        """Build the device program from the local committed-round store:
        the bootstrap path on the config controller and the TAKEOVER path
        on a promoted standby. Only committed rounds are ever in the
        store, so the replayed image is a valid post-commit state for
        every replica slot."""
        from ripplemq_tpu.broker.dataplane import replay_records

        log.info(
            "broker %d: booting data plane as controller (epoch %d, "
            "engine mode %s)",
            self.broker_id, self.manager.current_epoch(), self._engine_mode,
        )
        self.recorder.record("controller_boot",
                             epoch=self.manager.current_epoch(),
                             engine_mode=self._engine_mode)
        dp = None
        try:
            # The WHOLE boot sequence is one failure domain: a raise from
            # store replay (corrupt record), the DataPlane constructor
            # (boot-time lockstep failure — a worker dead when the plane
            # is (re)built raises from the configure broadcast BEFORE a
            # DataPlane exists, so the mid-call broken-plane path reading
            # dp.broken_reason never engages), install, the replicator,
            # or start must all count toward abdication — guarding only
            # the constructor would retry a doomed boot forever, and a
            # post-constructor raise would leak a constructed plane
            # (for spmd: workers already configured) into the next
            # attempt.
            image = None
            if self._round_store is not None:
                # Flush barrier BEFORE the replay scan: scan() may miss
                # (or stop torn at) a concurrently-appended tail, and a
                # promoted standby can be booting an instant after it
                # acked the deposed controller's LAST settled round —
                # that acked record must be in the replayed image or the
                # handover loses it (the seeded chaos soak caught
                # exactly this as an acked-produce loss: ack and
                # promotion 10 ms apart). After the local epoch bump
                # applied, the repl.rounds fence refuses the stale
                # stream, so nothing new lands mid-scan.
                self._round_store.flush()
                # Striped replication: a PROMOTED standby's store holds
                # stripe frames, not full rows — rebuild the committed
                # record stream from any k surviving stripes (local +
                # peers) and REWRITE the store to full records before
                # replay, so the booted controller serves reads below
                # trim and can catch up fresh standbys exactly like a
                # full-copy one (stripes/recovery.py; a short-of-k
                # non-tail group quarantines via CorruptStoreError, a
                # peers-unreachable shortfall retries the boot).
                self._rebuild_store_from_stripes()
                # Coverage holes in the recovered stream are rounds the
                # writing controller nacked (committed on device, never
                # settled): re-register them as settled gaps so the
                # booted plane keeps refusing to serve them
                # (replay_records gaps_out; ISSUE 4 residual window 2).
                gaps = {}
                # The producer-dedup table rides the same records
                # (REC_PIDSEQ): rebuilding it here is what keeps a
                # producer retry straddling this promotion exactly-once.
                pid_tab = {}
                image = replay_records(
                    self.config.engine, self._round_store.scan(),
                    gaps_out=gaps, pid_tab_out=pid_tab,
                )
            if self._waits:
                # An in-proc sibling may have pointed the process-global
                # timing at its own registry since __init__.
                self._time_locks()
            dp = DataPlane(
                self.config.engine, mode=self._engine_mode,
                store=self._round_store,
                workers=self._engine_workers or None,
                coalesce_s=self.config.coalesce_s,
                chain_depth=self.config.chain_depth,
                pipeline_depth=self.config.pipeline_depth,
                read_coalesce_s=self.config.read_coalesce_s,
                durability=self.config.durability,
                obs=self.config.obs,
                metrics=self.metrics,
                recorder=self.recorder,
                spans=self.spans,
            )
            if image is not None:
                dp.install(image, settled_gaps=gaps, pid_table=pid_tab)
            if self._round_store is not None:
                self._wire_replicator(dp)
            self._owns_dataplane = True
            self.dataplane = dp
            self.manager.attach_dataplane(dp)
            if self._started:
                dp.start()
        except Exception as e:
            if self._replicator is not None:
                self._replicator.stop()
                self._replicator = None
            if self.dataplane is dp:
                self.dataplane = None
                self.manager.detach_dataplane()
                self._owns_dataplane = False
            if dp is not None:
                try:
                    dp.stop()
                except Exception:
                    log.exception("stopping partially-booted plane")
            # A corrupt store can NEVER boot a plane, no matter how many
            # times the replay retries — quarantine it now (the boot-time
            # health walk only guards process start; damage surfacing at
            # promotion time otherwise crash-loops the takeover duty
            # forever, observed as ~1000 consecutive boot failures in the
            # proc disk-fault drills). The reopened-empty store routes
            # the next takeover tick through the quarantined-store path:
            # abdicate to a standby holding the real stream, or boot
            # empty as the genesis-equivalent last resort.
            from ripplemq_tpu.storage.segment import CorruptStoreError

            if (isinstance(e, CorruptStoreError) and self._owns_store
                    and self._store_dir is not None
                    and not self._store_quarantined):
                self._quarantine_store_midlife(e)
            # After a few consecutive failures (grace for a worker that
            # is merely still starting), abdicate the same way a
            # mid-call lockstep break does.
            self._boot_failures += 1
            self.recorder.record(
                "boot_failed", consecutive=self._boot_failures,
                error=f"{type(e).__name__}: {e}"[:200],
            )
            log.warning(
                "broker %d: data-plane boot failed (%d consecutive): "
                "%s: %s", self.broker_id, self._boot_failures,
                type(e).__name__, e,
            )
            if self._boot_failures >= 3:
                cmd = self.manager.plan_abdication()
                if cmd is not None:
                    log.warning(
                        "broker %d: abdicating controllership to broker "
                        "%d after repeated boot failures",
                        self.broker_id, cmd["controller"],
                    )
                    self.propose_cmd(cmd)
            raise
        self._boot_failures = 0
        # Compile hot programs before traffic needs them — EVERY bucket
        # this shape can hit, or the first big produce wave charges a
        # multi-second XLA compile to live traffic. On TAKEOVER
        # (epoch > 0) the first election pass is the latency-critical
        # device work — let it win the lock race before warming.
        self._warm_thread = dp.warm_async(
            buckets=dp.all_buckets(),
            delay_s=2.0 if self.manager.current_epoch() > 0 else 0.0,
        )

    def _wire_replicator(self, dp: DataPlane) -> None:
        """Attach a fresh replicator to the plane — the blocking
        replicate_fn plus its begin/wait split, which the plane's settle
        pipeline uses to keep a window of rounds streaming to the
        standbys while the device advances (dataplane settle pipeline)."""
        rep = self._make_replicator()
        rep.spans = self.spans
        dp.replicate_fn = rep.replicate
        dp.replicate_begin_fn = rep.begin
        dp.replicate_wait_fn = rep.wait
        push = getattr(rep, "push_floor", None)
        if self.config.follower_reads and push is not None:
            # The floor of a settled round goes to the standbys from
            # the release itself (replication.py `push_floor`); what it
            # costs the settle thread is settle.floor_push_us.
            hist = self.metrics.histogram("settle.floor_push_us")
            clock = self.metrics.clock

            def push_timed(slots: list) -> None:
                t0 = clock()
                push(slots)
                hist.observe(clock() - t0)

            dp.floor_push_fn = push_timed

    def _make_replicator(self):
        """Replication-plane factory: `replication="full"` streams full
        copies to every standby (RoundReplicator); `"striped"` encodes
        each group commit into k+m RS stripes shipped to distinct
        standbys and settles at any k stripe-acks (StripeReplicator —
        same begin/wait/catchup/suspects surface, (k+m)/k× the bytes
        instead of standby_count×)."""
        # The stream's fence reads come from the manager's fence view
        # (PartitionManager._publish_fence_view), WITHOUT its lock:
        # begin, the sender's frame stamp and wait are the serial path
        # every round crosses, and that lock is the one every consume
        # and commit handler queues on. Nothing else reads the view:
        # consume, commit, admission and the duties keep the locked
        # accessors (PERF.md section 6, PR 27 and PR 28).
        mgr, me = self.manager, self.broker_id

        def fence() -> tuple:
            v = mgr.fence_view
            return v.controller == me, v.epoch, v.standbys

        kw = dict(
            active_fn=lambda: fence()[0],
            epoch_fn=lambda: fence()[1],
            members_fn=lambda: fence()[2],
            rpc_timeout_s=min(2.0, self.config.rpc_timeout_s),
            ack_timeout_s=self.config.rpc_timeout_s,
            metrics=self.metrics,
            sender_id=self.broker_id,
            pipeline_depth=self.config.repl_pipeline_depth,
        )
        if self.config.replication == "striped":
            from ripplemq_tpu.stripes.plane import StripeReplicator

            self._replicator = StripeReplicator(
                self.client, self._addr_of,
                stripe_map_fn=self.manager.current_stripe_map,
                live_fn=self.manager.live_brokers,
                **kw,
            )
        else:
            from ripplemq_tpu.broker.replication import RoundReplicator

            self._replicator = RoundReplicator(
                self.client, self._addr_of,
                # Piggyback the per-slot settled floor (+ gap map) on
                # every repl.rounds frame — the full-copy follower read
                # plane's serve bound (striped frames already carry the
                # encoder's gsn floor in their header). Only when the
                # plane that reads the stamp exists on the standbys
                # (FollowerReadPlane.ingest_rounds, built under the same
                # knob): otherwise every frame would pay the manager's
                # and the plane's lock for a list nothing reads.
                floors_fn=(self._settle_floors_stamp
                           if self.config.follower_reads else None),
                fence_fn=fence,
                **kw,
            )
        return self._replicator

    def _settle_floors_stamp(self, slots):
        """RoundReplicator.floors_fn: the CURRENT controller plane's
        per-slot contiguous-settle floors + gap maps (empty when
        deposed — the frame then ships floor-less and standbys simply
        don't advance)."""
        dp = self._local_engine()
        if dp is None:
            return []
        return dp.settle_floors(slots)

    def _local_engine(self) -> Optional[DataPlane]:
        """The device program, iff this broker is the CURRENT controller
        (a deposed controller must not serve engine state it no longer
        replicates — fencing)."""
        dp = self.dataplane
        if dp is not None and self.manager.current_controller() == self.broker_id:
            return dp
        return None

    def _slo_degraded(self) -> bool:
        """The SLO shed machine's quorum-degradation signal. Like every
        shed signal it is ENGINE-SIDE (non-None only on the current
        controller — shedding exists to drain a queueing pipe, and the
        pipe lives here; see slo/controller.py for why a frontend-local
        p99 signal was deliberately removed): an engine partition lost
        its replica quorum, OR controller failover is armed
        (standby_count > 0) and the replicated standby set is EMPTY —
        in that state the settle path refuses every round (the PR 2
        empty-set fence), so refusing cheaply at admission is strictly
        kinder than queueing produces into certain refusal."""
        dp = self._local_engine()
        if dp is None:
            return False
        if dp.degraded_slots():
            return True
        if self.config.standby_count <= 0 or self._round_store is None:
            return False
        if self.manager.current_standbys():
            # Arm the empty-set signal only once a standby EVER joined
            # (the replicator's _had_members rule): genesis settles
            # member-less by design, and shedding a freshly-booted
            # cluster for not yet having standbys would be a
            # self-inflicted outage.
            self._slo_had_standbys = True
            return False
        return self._slo_had_standbys

    def _addr_of(self, broker_id: int) -> str:
        return self.config.broker(broker_id).address

    def _time_locks(self) -> None:
        from ripplemq_tpu.obs import lockwitness

        lockwitness.enable_timing(self.metrics, self.recorder)

    def start(self) -> None:
        self._started = True
        if self._wake_probe is not None:
            self._wake_probe.start()
        if self._net is not None:
            self._net.register(self.addr, self.dispatch)
        else:
            self._tcp_server.start()
        if self.dataplane is not None and self._owns_dataplane:
            self.dataplane.start()
        self.runner.start()
        self._duty_thread.start()
        self.slo.start()

    @property
    def stopped(self) -> bool:
        """True once stop() ran (or before __init__ completed) — the
        liveness probe harnesses poll instead of reaching into
        `_stopped` bare."""
        return self._stopped

    def stop(self) -> None:
        # Idempotent: a killed-but-never-restarted broker is stopped
        # again by harness/cluster teardown, and the second pass must
        # not flush the segment store the first one closed. Initialized
        # True at the TOP of __init__ (and flipped False at its end), so
        # teardown after a partial __init__ failure is a no-op instead
        # of a crash against half-constructed state.
        if self._stopped:
            return
        self._stopped = True
        self._stop.set()
        if self.dataplane is not None:
            # Fetches parked on the plane hold RPC workers of this
            # broker: refused now, not at their deadlines.
            self.dataplane.release_parks()
        if self.follower_plane is not None:
            self.follower_plane.release_parks("stopped")
        if self._wake_probe is not None:
            from ripplemq_tpu.obs import lockwitness

            self._wake_probe.stop()
            lockwitness.disable_timing(self.metrics)
        # Release handlers parked on un-proposed waves before joining
        # the duty thread (their RPC workers would otherwise hold the
        # full waiter timeout).
        self._fail_pending_waves()
        self.slo.stop()
        # A constructed-but-never-started broker (start() raised, or a
        # launcher's cleanup after a failed boot) has no duty thread to
        # join — joining an unstarted Thread raises.
        if self._duty_thread.ident is not None:
            self._duty_thread.join(timeout=2)
        self.runner.stop()
        if self._net is not None:
            self._net.unregister(self.addr)
        else:
            self._tcp_server.stop()
        if self._replicator is not None:
            self._replicator.stop()
        if self.dataplane is not None and self._owns_dataplane:
            self.dataplane.stop()
        if self._owns_store and self._round_store is not None:
            self._round_store.close()
        self.client.close()
        self._raft_client.close()

    # ------------------------------------------------------------- dispatch

    def dispatch(self, req: dict) -> dict:
        resp = self._dispatch(req)
        if isinstance(resp, dict):
            # Every response names its serving broker: clients and the
            # chaos history checker attribute outcomes to a concrete
            # broker when reconstructing a failure (who acked this
            # produce, whose view served this read).
            resp.setdefault("broker", self.broker_id)
        return resp

    def _dispatch(self, req: dict) -> dict:
        t = req.get("type", "")
        try:
            if t in RAFT_TYPES:
                return self.runner.handle_rpc(req)
            if t == "meta.topics":
                # Topics + broker roster: clients resolve leader broker ids
                # to advertised addresses from here (the reference instead
                # parsed "brokerN" out of hostnames and substituted
                # bootstrap entries — ProducerClientImpl.java:101-107; that
                # hack is deliberately not reproduced).
                return {
                    "ok": True,
                    "topics": topics_to_wire(self.manager.get_topics()),
                    "brokers": [b.to_dict() for b in self.config.brokers],
                    # Follower-read advertisement: which standbys hold a
                    # consume lease, and under which controller epoch —
                    # the client SDK routes explicit-offset consumes to
                    # a leased broker and falls back to the leader on
                    # `not_settled_here:` refusals. Empty dict when the
                    # feature is off or no lease is granted.
                    "follower_leases": {
                        str(b): int(e)
                        for b, e in
                        self.manager.current_follower_leases().items()
                    },
                    "controller_epoch": self.manager.current_epoch(),
                    # The row cap of one round per partition: what a
                    # batching producer fills a produce.multi part to.
                    "max_batch": self.config.engine.max_batch,
                    # Broker id -> rack, where the cluster names racks
                    # (Kafka's broker.rack): a consumer with a
                    # `client_rack` keeps its session with the leased
                    # follower of its own.
                    **({"broker_racks": {str(b): r for b, r in
                                         self.config.broker_racks}}
                       if self.config.broker_racks else {}),
                }
            if t == "meta.propose":
                return self._handle_meta_propose(req)
            if t == "produce":
                return self._handle_produce(req)
            if t == "produce.multi":
                return self._handle_produce_multi(req)
            if t == "consume":
                return self._handle_consume(req)
            if t == "consume.multi":
                return self._handle_consume_multi(req)
            if t == "offset.commit":
                return self._handle_offset_commit(req)
            if t == "offset.commit.multi":
                return self._handle_offset_commit_multi(req)
            if t == "producer.register":
                return self._handle_producer_register(req)
            if t.startswith("group."):
                return self._handle_group(t, req)
            if t == "repl.rounds":
                return self._handle_repl_rounds(req)
            if t == "repl.stripes":
                return self._handle_repl_stripes(req)
            if t == "stripe.fetch":
                return self._handle_stripe_fetch(req)
            if t == "admin.split":
                return self._handle_admin_split(req)
            if t == "admin.merge":
                return self._handle_admin_merge(req)
            if t == "admin.stats":
                return self._handle_stats(req)
            if t == "admin.metrics":
                return self._handle_metrics(req)
            if t == "admin.metrics_text":
                return self._handle_metrics_text(req)
            if t == "admin.trace":
                return self._handle_trace(req)
            if t == "admin.spans":
                return self._handle_spans(req)
            if t == "admin.postmortem":
                from ripplemq_tpu.obs.postmortem import collect_postmortem

                return collect_postmortem(self)
            if t.startswith("shard."):
                return self._handle_shard(t, req)
            if t.startswith("engine."):
                return self._handle_engine(t, req)
            return {"ok": False, "error": f"unknown request type {t!r}"}
        except _UpstreamRefusal as e:
            return dict(e.resp)
        except NotCommittedError as e:
            return {"ok": False, "error": f"not_committed: {e}"}
        except ConsumerTableFullError as e:
            # Permanent refusal, NOT retryable (not_committed implies
            # retry): the client must pick a committed-and-released name
            # or the operator must raise max_consumers.
            return {"ok": False, "error": f"consumer_table_full: {e}"}
        except (KeyError, ValueError, TypeError) as e:
            return {"ok": False, "error": f"bad_request: {type(e).__name__}: {e}"}

    # -- observability -----------------------------------------------------

    def _handle_metrics(self, req: dict) -> dict:
        """The metrics-registry snapshot (counters/gauges/log-bucketed
        histogram summaries — obs/metrics.py) plus the process-global
        wire-codec frame stats. Cheap enough to poll; the heavyweight
        one-shot diagnosis surface is admin.postmortem."""
        del req
        from ripplemq_tpu.wire import codec as _codec

        out = {
            "ok": True,
            "obs": self.config.obs,
            "metrics": self.metrics.snapshot(),
            # Codec stats are PROCESS-global (the codec is stateless
            # module functions): in an in-proc multi-broker cluster they
            # aggregate across every broker sharing the process.
            "wire": _codec.codec_stats(),
        }
        dp = self._local_engine()
        if dp is not None and dp.metrics is not self.metrics:
            # An externally-injected plane keeps its own registry.
            out["engine_metrics"] = dp.metrics.snapshot()
        return out

    def _handle_metrics_text(self, req: dict) -> dict:
        """Prometheus-style text exposition of the SAME registry
        admin.metrics snapshots (obs/metrics.py render_prometheus):
        counters as `_total`, gauges bare, histograms as cumulative
        log2 `_bucket{le=...}` series with `_sum`/`_count`. One string
        under "text" so both transports carry it as an ordinary
        response field; scrape adapters write it out verbatim."""
        from ripplemq_tpu.obs.metrics import render_prometheus

        text = render_prometheus(self.metrics)
        dp = self._local_engine()
        if dp is not None and dp.metrics is not self.metrics:
            text += render_prometheus(dp.metrics)
        return {"ok": True, "text": text}

    def _handle_spans(self, req: dict) -> dict:
        """Paged span-ring read (obs/spans.py), the collection half of
        the causal-tracing plane. Same paging contract as stripe.fetch:
        `after` is the last seq the caller saw (-1 from cold),
        `max_spans` bounds the page, and the response's `cursor` is the
        last served record's seq (== `after` when the page is empty).
        Rings are racy-consistent; assemblers page until the cursor
        stops moving. trace_sample_n=0 serves empty pages, not errors.

        Beside the page: the ring's loss contract (`first_seq`, the
        oldest seq held, and `dropped`, the records past `after` that
        were overwritten before this read — SpanRing.page) and one
        `clock` anchor, the serving process's three clocks read back to
        back: `perf_counter` (the span ring's and the registry's),
        `monotonic_ns` and `time_ns` (a profiler trace's). A reader
        places this broker's spans on another clock through the anchor
        and never assumes the three coincide; nothing in the tracing
        plane itself compares them."""
        after = int(req.get("after", -1))
        clock = {"perf_counter": time.perf_counter(),
                 "monotonic_ns": time.monotonic_ns(),
                 "time_ns": time.time_ns()}
        if self.spans is None:
            return {"ok": True, "spans": [], "cursor": after,
                    "first_seq": 0, "dropped": 0, "clock": clock}
        max_spans = req.get("max_spans")
        page = self.spans.page(
            after=after,
            max_spans=int(max_spans) if max_spans is not None else None,
        )
        return {"ok": True, "clock": clock, **page}

    def _handle_trace(self, req: dict) -> dict:
        """The flight-recorder window (obs/trace.py), oldest first;
        `last` clips to the most recent N events."""
        last = req.get("last")
        last = int(last) if last is not None else None
        # `now` is this broker's wall clock at snapshot time: the chaos
        # timeline merge pairs it with the caller's send/receive stamps
        # (NTP midpoint) to estimate per-broker clock skew instead of
        # trusting raw wall-clock event ordering across processes.
        out = {"ok": True, "trace": self.recorder.snapshot(last=last),
               "now": time.time()}
        dp = self._local_engine()
        if dp is not None and dp.recorder is not self.recorder:
            out["engine_trace"] = dp.recorder.snapshot(last=last)
        return out

    def _handle_stats(self, req: dict) -> dict:
        """Broker stats/health snapshot: metadata role, controller state,
        per-partition leadership, engine counters (controller only), and
        the duty/erasure error rings. The reference's observability is a
        log4j2 console stack (log4j2.xml:10-14); this adds the health
        endpoint it lacked. `slots` (optional list) selects partitions
        for per-slot engine detail (commit index, absolute end, trim)."""
        node = self.runner.node
        topics = {}
        for t in self.manager.get_topics():
            topics[t.name] = {
                str(a.partition_id): {
                    "leader": a.leader, "term": a.term,
                    "replicas": list(a.replicas),
                    # Elastic-partition surface: reconfiguration
                    # generation, owned key-hash range, lifecycle state
                    # (active | handoff | retired), parent pid for
                    # split children (-1 = configured partition).
                    "generation": a.generation,
                    "range": [a.range_lo, a.range_hi],
                    "state": a.state,
                    "origin": a.origin,
                }
                for a in t.assignments
            }
        stats = {
            "ok": True,
            "broker": self.broker_id,
            "address": self.addr,
            # Consecutive data-plane boot failures (genesis or takeover;
            # reset on success and on losing controllership) — makes a
            # boot-retry loop operator-visible instead of log-only.
            "boot_failures": self._boot_failures,
            # True while the local committed-round store is a fresh
            # replacement for a boot-time-quarantined one (disk damage
            # beyond erasure repair); clears once standby catch-up
            # re-transfers the full prefix.
            "store_quarantined": self._store_quarantined,
            # Whether the local round store writes through the native
            # (C++) segment writer: a failed native build degrades to
            # the Python writer silently everywhere else.
            "store_native": bool(
                getattr(self._round_store, "is_native", False)
            ),
            "metadata": {
                "role": node.role,
                "term": node.term,
                "leader_hint": node.leader_hint,
            },
            "controller": {
                "id": self.manager.current_controller(),
                "epoch": self.manager.current_epoch(),
                "standbys": list(self.manager.current_standbys()),
                "is_self": self.is_controller,
            },
            "topics": topics,
            "live": list(self.manager.live),
            # Consumer groups: per-group generation + membership (the
            # coordinator's replicated view — identical on every broker).
            "groups": self.manager.groups_summary(),
            # Idempotent-producer registry size (issued pids, including
            # broker-stamping pids) and recycled slots awaiting reset.
            "producer_ids": len(self.manager.producers),
            "dirty_consumer_slots": self.manager.dirty_slots(),
            "duty_errors": list(self.duty_errors),
            "erasure_errors": list(
                getattr(self._round_store, "erasure_errors", [])
            ),
            # Striped replication surface: the active replication plane,
            # the replicated stripe→member assignment (stripe i held by
            # stripe_holders[i]; empty before a standby joins or in
            # full-copy mode), and how many any-k promotion rebuilds
            # this process has run (stripes/recovery.py).
            "stripe_mode": self.config.replication,
            "stripe_holders": [
                int(b) for b in self.manager.current_stripe_map()
            ],
            "stripe_rebuilds": self._stripe_rebuilds,
        }
        # Control-plane wave batching + heartbeat relay: how many
        # OP_BATCH waves this broker formed, the sub-commands they
        # carried (proposals_saved = events - waves: raft proposals the
        # coalescing avoided), the wave-size histogram (pow2 buckets),
        # and the relay plane's counters — beats answered locally,
        # frames delivered, stamps ingested while leading.
        with self._intake_lock:
            intake_depth = len(self._intake)
        stats["control_plane"] = {
            "waves": self._wave_count,
            "wave_events": self._wave_events,
            "wave_failures": self._wave_failures,
            "wave_size_hist": dict(self._wave_size_hist),
            "proposals_saved": self._wave_events - self._wave_count,
            "intake_depth": intake_depth,
            "heartbeats_local": self._heartbeats_local,
            "beat_frames": self._beat_frames,
            "beats_relayed": self._beats_relayed,
        }
        # SLO autopilot: mode, current knob values, shed/refusal counts,
        # and the tick/transition history chaos verdicts replay
        # (`enabled: false` shape when the loop is off — the admission
        # counters still live there, quotas work without the loop).
        stats["slo"] = self.slo.stats()
        # Elastic partitions: the replicated split/merge topology
        # (children, retired, open handoff windows, spare-slot pool)
        # plus THIS broker's local reconfiguration counters — dual-
        # write forwards it served as a handoff leader, generation-
        # fence refusals it answered. The chaos reconfig verdict reads
        # this block on every broker and sums the local halves.
        reconfig = self.manager.reconfig_stats()
        reconfig["forwarded_writes"] = self._forwarded_writes
        reconfig["fence_refusals"] = self._gen_fence_refusals
        stats["reconfig"] = reconfig
        # Follower read plane: lease table + this broker's own serving
        # counters (floor lag, cache hit rate, reads served/refused).
        # `enabled: false` shape when the knob is off — the lease keys
        # are still present so dashboards need no conditional.
        follower = {
            "enabled": self.config.follower_reads,
            "lease_epoch": self.manager.follower_lease(self.broker_id),
            "leases": {
                str(b): int(e)
                for b, e in self.manager.current_follower_leases().items()
            },
        }
        if self.follower_plane is not None:
            follower.update(self.follower_plane.stats())
        stats["follower"] = follower
        dp = self._local_engine()
        if dp is None:
            stats["engine"] = None
        else:
            engine = {
                "mode": self._engine_mode,
                # What the engine programs actually run on (platform,
                # device kind/count, compiled write phase, replica →
                # device placement, peak device memory).
                "device": dp.device_stats(),
                "rounds": dp.rounds,
                "dispatches": dp.dispatches,
                "read_queries": dp.read_queries,
                "read_dispatches": dp.read_dispatches,
                "read_cache_hits": dp.read_cache_hits,
                # Slots whose host mirror is gap-disabled (resolve
                # failure; pending trim-passage heal) — a silent cache
                # regression the operator should be able to see. Read
                # through the locked accessor: the resolver mutates the
                # gap dict concurrently.
                "mirror_gap_slots": dp.mirror_gap_slots(),
                # Slots carrying settled gaps (replication-FAILED rounds
                # every read path skips) — same locked-accessor pattern.
                "settled_gap_slots": dp.settled_gap_slots(),
                # Slots whose recent rounds ALL failed to commit on
                # device (the term-skew wedge probe feeding the duty's
                # re-election gate) — non-empty here means the duty is
                # about to heal, or the partition has no engine quorum.
                "stalled_slots": dp.stalled_slots(),
                "committed_entries": dp.committed_entries,
                "step_errors": dp.step_errors,
                # Settle-pipeline occupancy (pipelined standby
                # replication): window width, mean depth at enqueue,
                # and how often dispatch hit the window's backpressure.
                "settle": dp.settle_stats(),
                "partitions": dp.cfg.partitions,
                # Graceful-degradation surface: partitions whose replica
                # quorum is lost fast-fail consumes/commits with
                # `unavailable` instead of hanging; the flag makes that
                # state operator-visible before the first refusal.
                "degraded_slots": dp.degraded_slots(),
                # Producer-dedup table occupancy ((pid, partition) keys):
                # the idempotence plane's memory footprint, and a rough
                # count of distinct producer streams the broker has
                # settled.
                "pid_table_size": dp.pid_table_size(),
            }
            engine["degraded"] = bool(engine["degraded_slots"])
            slots = req.get("slots")
            if slots:
                # One device fetch for ALL requested slots (a per-slot
                # commit_index() loop would sync the device — and stall
                # the round pipeline — once per slot); shadow + trim are
                # snapshotted consistently under the plane's lock.
                engine["slots"] = dp.slot_detail(slots)
            stats["engine"] = engine
        return stats

    # -- distributed erasure shards ---------------------------------------
    # Each broker pushes its sealed segments' RS shards to peers (round-
    # robin over the roster), and on boot refills missing shard sets from
    # peers before the local repair pass — so losing a broker's disk
    # entirely (segments AND local shards) is recoverable from any K of
    # the K+M distributed shard copies. The reference's only equivalent
    # is full per-broker replication (PartitionRaftServer.java:88-90);
    # this gets the same any-K-of-N durability at (K+M)/K x overhead.

    def _peer_dir_for(self, owner: int) -> Optional[str]:
        if self._peer_shard_dir is None:
            return None
        import os

        return os.path.join(self._peer_shard_dir, f"broker-{int(owner)}")

    def _handle_shard(self, t: str, req: dict) -> dict:
        import os

        from ripplemq_tpu.storage.erasure import valid_shard_name

        d = self._peer_dir_for(int(req["owner"]))
        if d is None:
            return {"ok": False, "error": "no_data_dir"}
        if t == "shard.put":
            name = str(req["name"])
            if not valid_shard_name(name):
                return {"ok": False,
                        "error": f"bad_request: shard name {name!r}"}
            os.makedirs(d, exist_ok=True)
            tmp = os.path.join(d, name + ".tmp")
            with open(tmp, "wb") as f:
                f.write(req["data"])
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, os.path.join(d, name))
            return {"ok": True}
        if t == "shard.list":
            names = []
            if os.path.isdir(d):
                names = sorted(
                    f for f in os.listdir(d)
                    if valid_shard_name(f)
                )
            return {"ok": True, "shards": names}
        if t == "shard.get":
            name = str(req["name"])
            if not valid_shard_name(name):
                return {"ok": False,
                        "error": f"bad_request: shard name {name!r}"}
            try:
                with open(os.path.join(d, name), "rb") as f:
                    return {"ok": True, "data": f.read()}
            except OSError:
                return {"ok": False, "error": "not_found"}
        if t == "shard.drop":
            name = str(req["name"])
            if not valid_shard_name(name):
                return {"ok": False,
                        "error": f"bad_request: shard name {name!r}"}
            try:
                os.remove(os.path.join(d, name))
            except OSError:
                pass  # already gone: drop is idempotent
            return {"ok": True}
        return {"ok": False, "error": f"unknown shard op {t!r}"}

    def _validate_or_quarantine_store(self) -> None:
        """Boot-time store health gate (after peer refill + erasure
        repair): a store the scanners would refuse — a CRC-failing
        record beyond the torn-tail contract, or a sealed segment FILE
        still missing after both recovery passes — is moved aside
        (`segments.quarantine-N`) and the broker reopens EMPTY. It then
        rejoins as a standby and re-replicates the full committed-round
        stream through the catch-up protocol; recovered-metadata
        controllership over a quarantined store is refused by the
        takeover duty (an emptied store must never boot a plane that
        would serve an empty history as truth). Never crash-loop, never
        serve a row that fails CRC."""
        from ripplemq_tpu.storage.erasure import segment_index_gaps
        from ripplemq_tpu.storage.segment import (
            CorruptStoreError,
            quarantine_store,
            verify_store,
        )

        try:
            if segment_index_gaps(self._store_dir):
                raise CorruptStoreError(
                    "sealed segment files missing after refill + repair"
                )
            # repair_torn_tail: the reopen below starts a NEW segment, so
            # a merely-tolerated torn tail would seal into a segment every
            # later scan refuses — truncate it off while it is still legal.
            verify_store(self._store_dir, repair_torn_tail=True)
        except CorruptStoreError as e:
            target = quarantine_store(self._store_dir)
            self._store_quarantined = True
            self.recorder.record("store_quarantine", when="boot",
                                 error=str(e)[:200])
            log.warning(
                "broker %d: store failed its boot health walk (%s); "
                "quarantined to %s — reopening empty, will re-replicate "
                "via standby catch-up", self.broker_id, e, target,
            )

    def _quarantine_store_midlife(self, cause: Exception) -> None:
        """Quarantine a store whose damage surfaced AFTER boot (a replay
        scan raising mid-promotion) and reopen it empty. Same contract
        as the boot-time gate: the damaged bytes move aside for
        forensics, `_store_quarantined` keeps the takeover duty from
        booting a plane that would serve the emptied history as truth,
        and the flag clears once standby catch-up re-admits this broker
        with the full stream. Concurrent repl appends against the OLD
        store object fail harmlessly (their segment paths moved) and the
        controller's retry lands on the fresh store."""
        from ripplemq_tpu.storage.segment import (
            SegmentStore,
            quarantine_store,
        )

        try:
            self._round_store.close()
        except Exception:
            log.exception("closing store ahead of mid-life quarantine")
        target = quarantine_store(self._store_dir)
        self._store_quarantined = True
        self._quarantine_left_set = False
        self.recorder.record("store_quarantine", when="midlife",
                             error=f"{type(cause).__name__}: {cause}"[:200])
        self._round_store = SegmentStore(
            self._store_dir, erasure=True,
            segment_bytes=self.config.segment_bytes,
            retention_bytes=self.config.store_retention_bytes,
            metrics=self.metrics,
        )
        log.warning(
            "broker %d: store failed its replay scan mid-life (%s: %s); "
            "quarantined to %s — reopening empty, will re-replicate via "
            "standby catch-up", self.broker_id, type(cause).__name__,
            cause, target,
        )

    def _rebuild_store_from_stripes(self) -> None:
        """Striped-promotion rebuild: if the local store holds
        REC_STRIPE frames (this broker lived as a striped standby),
        gather the missing stripe indices from live peers
        (stripe.fetch), reconstruct every group's records from any k
        of its k+m stripes, and REWRITE the store as a plain full-
        record store (previous bytes kept at `segments.prestripe-N`
        for forensics). No-op when the store has no stripes (ordinary
        controller restart, full-copy mode, genesis).

        Failure ladder (rebuild-or-quarantine, PR 4): a group short of
        k with some peer unreachable raises StripeRecoveryError — the
        takeover duty retries next tick and repeated failures abdicate;
        short of k with EVERY peer consulted raises CorruptStoreError,
        routing into the existing quarantine machinery (non-tail only
        — a torn tail of never-settled groups is dropped)."""
        from ripplemq_tpu.storage.segment import (
            REC_STRIPE,
            CorruptStoreError,
            SegmentStore,
        )
        from ripplemq_tpu.stripes.recovery import (
            StripeDataLossError,
            rebuild_records,
        )

        store = self._round_store
        if store is None:
            return
        if not any(rec[0] == REC_STRIPE for rec in store.scan()):
            return
        self._stripe_rebuilds += 1
        self.recorder.record("stripe_rebuild",
                             epoch=self.manager.current_epoch())

        def mk_fetch(addr):
            def fetch(after):
                resp = self.client.call(
                    addr, {"type": "stripe.fetch", "after": after},
                    timeout=min(10.0, 2 * self.config.rpc_timeout_s),
                )
                if not resp.get("ok"):
                    raise RpcError(
                        f"stripe.fetch refused: {resp.get('error')}"
                    )
                return resp.get("frames", []), resp.get("next")
            return fetch

        fetchers = [
            (b.address, mk_fetch(b.address))
            for b in self.config.brokers
            if b.broker_id != self.broker_id
        ]
        try:
            records = rebuild_records(store.scan(), fetchers)
        except StripeDataLossError as e:
            raise CorruptStoreError(f"stripe rebuild: {e}") from e
        log.info(
            "broker %d: rebuilt %d full records from stripe store "
            "(rebuild #%d)", self.broker_id, len(records),
            self._stripe_rebuilds,
        )
        if self._store_dir is None:
            # In-memory store (in-proc cluster without a data dir):
            # rewrite in place.
            from ripplemq_tpu.storage.memstore import MemoryRoundStore

            fresh = MemoryRoundStore()
            for rec in records:
                fresh.append(*rec)
            self._round_store = fresh
            return
        import os

        tmp = self._store_dir + ".restripe"
        if os.path.exists(tmp):
            import shutil

            shutil.rmtree(tmp)
        out = SegmentStore(tmp, segment_bytes=self.config.segment_bytes)
        try:
            for i in range(0, len(records), 256):
                out.append_many(records[i : i + 256])
        finally:
            out.close()
        store.close()
        n = 0
        while os.path.exists(f"{self._store_dir}.prestripe-{n}"):
            n += 1
        os.replace(self._store_dir, f"{self._store_dir}.prestripe-{n}")
        os.replace(tmp, self._store_dir)
        self._round_store = SegmentStore(
            self._store_dir, erasure=True,
            segment_bytes=self.config.segment_bytes,
            retention_bytes=self.config.store_retention_bytes,
            metrics=self.metrics,
        )

    def _refill_shards_from_peers(self) -> None:
        """Boot-time disaster recovery: pull peer-held shard copies for
        sealed segments this store lost (see refill_from_peers). Gated on
        LOCAL loss evidence — a hole in the store's contiguous segment
        numbering — so ordinary boots (including cold cluster starts,
        where peers aren't serving yet) skip the peer round-trips
        entirely. A fully wiped data dir shows no holes and recovers
        through the committed-round replication stream instead
        (broker/replication.py standby catch-up)."""
        from ripplemq_tpu.storage.erasure import (
            refill_from_peers,
            segment_index_gaps,
        )

        if not segment_index_gaps(self._store_dir):
            return
        peers = [
            b for b in self.config.brokers if b.broker_id != self.broker_id
        ]
        if not peers:
            return

        def mk_list(addr):
            def f():
                resp = self.client.call(
                    addr, {"type": "shard.list", "owner": self.broker_id},
                    timeout=2.0,
                )
                return resp.get("shards", []) if resp.get("ok") else []
            return f

        def get(addr, name):
            resp = self.client.call(
                addr,
                {"type": "shard.get", "owner": self.broker_id, "name": name},
                timeout=5.0,
            )
            return resp.get("data") if resp.get("ok") else None

        try:
            refilled = refill_from_peers(
                self._store_dir,
                [(b.address, mk_list(b.address)) for b in peers],
                get,
            )
        except Exception as e:  # never block boot on the disaster path
            log.warning("broker %d: shard refill failed: %s: %s",
                        self.broker_id, type(e).__name__, e)
            return
        if refilled:
            log.info("broker %d: refilled shard sets from peers for %s",
                     self.broker_id, refilled)

    def _seed_pushed_shards(self) -> None:
        """One-time (per boot) sync of the pushed-set with what peers
        already hold, so a restart does not re-transfer the whole sealed
        history. Peer-held shards for segments below our persisted GC
        floor are stale (the drop may have been missed across a
        restart): ask those peers to drop them instead."""
        from ripplemq_tpu.storage.erasure import valid_shard_name
        from ripplemq_tpu.storage.segment import gc_floor, segment_index

        floor = gc_floor(self._store_dir)
        for b in self.config.brokers:
            if b.broker_id == self.broker_id:
                continue
            try:
                resp = self.client.call(
                    b.address,
                    {"type": "shard.list", "owner": self.broker_id},
                    timeout=2.0,
                )
            except RpcError:
                continue  # unreachable: worst case a redundant re-push
            if not resp.get("ok"):
                continue
            for name in resp.get("shards", []):
                if not valid_shard_name(name):
                    continue
                if segment_index(name.rpartition(".shard")[0]) < floor:
                    try:
                        self.client.call(
                            b.address,
                            {"type": "shard.drop",
                             "owner": self.broker_id, "name": name},
                            timeout=2.0,
                        )
                    except RpcError:
                        pass
                else:
                    self._pushed_shards.add(name)

    def _gc_duty(self) -> None:
        """Size-capped store retention: delete the oldest sealed
        segments past store_retention_bytes, prune the controller's
        retention indexes, and tell the peers holding those segments'
        distributed shards to drop their copies."""
        gc = getattr(self._round_store, "gc", None)
        if gc is None:
            return
        deleted = gc()
        if not deleted:
            return
        log.info("broker %d: store GC deleted segments %s",
                 self.broker_id, deleted)
        if self.dataplane is not None:
            self.dataplane.drop_index_segments(set(deleted))
        # Peer copies of the deleted segments' shards are now garbage.
        from ripplemq_tpu.storage.segment import segment_name

        stems = {segment_name(i) for i in deleted}
        gone = {
            n for n in self._pushed_shards
            if n.rpartition(".shard")[0] in stems
        }
        self._pushed_shards -= gone
        # Queue drops for every eligible peer: the push target rotation
        # (including bad-target skips) means we cannot know which peer
        # holds a given shard, and drop is idempotent+cheap — but a big
        # GC can queue hundreds, so the shared duty loop drains them a
        # few per tick (_drain_shard_drops) instead of stalling failover
        # duties behind sequential RPC timeouts.
        for name in gone:
            for b in self.config.brokers:
                if (b.broker_id == self.broker_id
                        or b.broker_id in self._bad_shard_targets):
                    continue
                self._pending_shard_drops.append((b.broker_id, name))

    def _drain_shard_drops(self, budget: int = 4) -> None:
        while budget > 0 and self._pending_shard_drops:
            target, name = self._pending_shard_drops.pop(0)
            budget -= 1
            try:
                self.client.call(
                    self._addr_of(target),
                    {"type": "shard.drop", "owner": self.broker_id,
                     "name": name},
                    timeout=2.0,
                )
            except RpcError:
                pass  # best-effort: peer copies are derived data

    def _shard_duty(self) -> None:
        """Push not-yet-distributed local shard files to their designated
        peers (shard i of a segment goes to the (i+1)-th broker after
        this one in the roster — with K+M=5 shards and >=5 brokers each
        lands on a distinct peer). Work per tick is bounded by ATTEMPTS
        (a partitioned peer's timeouts must not stall the duty loop that
        also runs failover duties), and peers that refuse storage
        (no_data_dir) rotate to the next roster member."""
        if self._store_dir is None:
            return
        now = time.monotonic()
        if now - self._last_shard_push < 2.0:
            return
        protect = getattr(self._round_store, "protect_async", None)
        if protect is not None:
            protect()  # traffic-independent encode trigger (see method)
        if not self._shard_push_seeded:
            # Seed BEFORE the first GC pass: drops for already-GC'd
            # segments are computed from the pushed-set, which must
            # reflect what peers actually hold.
            self._shard_push_seeded = True
            self._seed_pushed_shards()
        self._gc_duty()
        self._drain_shard_drops()
        self._last_shard_push = now
        import os

        from ripplemq_tpu.storage.erasure import shard_file_names

        roster = [b.broker_id for b in self.config.brokers]
        if len(roster) < 2:
            return
        my = roster.index(self.broker_id)
        attempts = 0
        for name in shard_file_names(self._store_dir):
            if name in self._pushed_shards:
                continue
            if attempts >= 4:
                break  # bound per-tick work/stall (duty loop is shared)
            idx = int(name.rpartition(".shard")[2])
            candidates = [
                roster[(my + 1 + idx + k) % len(roster)]
                for k in range(len(roster))
            ]
            targets = [
                t for t in candidates
                if t != self.broker_id and t not in self._bad_shard_targets
            ]
            if not targets:
                break  # every peer refuses storage; nothing to do
            path = os.path.join(self._store_dir, "rs", name)
            lap = self._st_shard_put.timed()  # closed with the answer
            try:
                with open(path, "rb") as f:
                    blob = f.read()
            except OSError:
                continue
            attempts += 1
            try:
                with lap:
                    resp = self.client.call(
                        self._addr_of(targets[0]),
                        {"type": "shard.put", "owner": self.broker_id,
                         "name": name, "data": blob},
                        timeout=self.config.rpc_timeout_s,
                    )
            except RpcError:
                continue  # peer down; retried next pass
            if resp.get("ok"):
                self._pushed_shards.add(name)
            elif resp.get("error") == "no_data_dir":
                # Storage-less peer: never a valid target.
                self._bad_shard_targets.add(targets[0])

    # -- metadata ----------------------------------------------------------

    def _handle_meta_propose(self, req: dict) -> dict:
        node = self.runner.node
        if node.role != LEADER:
            hint = node.leader_hint
            return {
                "ok": False,
                "error": "not_leader",
                "leader": hint,
                "leader_addr": self._addr_of(hint) if hint is not None else None,
            }
        index = self.runner.propose(req["cmd"])
        if index is None:
            return {"ok": False, "error": "not_leader", "leader": None}
        return {"ok": True, "index": index}

    def _propose_retry_policy(self, retries: int) -> RetryPolicy:
        """Retry spacing for leader-forwarded proposals. The backoff CAP
        tracks the metadata election timeout, not just the duty
        interval: a leaderless blip lasts about one metadata election,
        and a cap well below it (the old duty-interval-scaled 0.5 s
        ceiling) burned every attempt back-to-back before a new leader
        could exist. Jitter rides the shared RetryPolicy defaults so
        concurrent proposers decorrelate instead of thundering the
        fresh leader together. Extracted so the spacing is directly
        testable (tests/test_group_waves.py)."""
        return RetryPolicy(
            max_attempts=retries,
            base_backoff_s=max(
                self._duty_interval_s,
                self.config.metadata_election_timeout_s / 8,
            ),
            max_backoff_s=max(
                self._duty_interval_s, 0.5,
                self.config.metadata_election_timeout_s,
            ),
            deadline_s=self.config.rpc_timeout_s * max(1, retries),
        )

    def propose_cmd(self, cmd: dict, retries: int = 3) -> bool:
        """Propose a metadata command, forwarding to the metadata leader if
        this broker is not it (the reference's forwarding-with-retries,
        PartitionManager.java:219-246). Retries ride the same unified
        RetryPolicy as the clients (wire/retry.py): jittered exponential
        backoff spaced to the metadata election timescale
        (_propose_retry_policy), the whole operation bounded by one
        rpc-timeout deadline budget — a partitioned metadata leader
        costs a bounded stall, not retries x timeout."""
        policy = self._propose_retry_policy(retries)
        run = policy.begin()
        while run.attempt():
            node = self.runner.node
            if node.role == LEADER:
                if self.runner.propose(cmd) is not None:
                    return True
                run.note("local propose refused (lost leadership?)")
            else:
                hint = node.leader_hint
                if hint is not None and hint != self.broker_id:
                    try:
                        resp = self._raft_client.call(
                            self._addr_of(hint),
                            {"type": "meta.propose", "cmd": cmd},
                            timeout=run.clip(self.config.rpc_timeout_s),
                        )
                        if resp.get("ok"):
                            return True
                        run.note(str(resp.get("error", "")))
                    except RpcError as e:
                        run.note(str(e))
                else:
                    run.note("no metadata leader hint")
        return False

    # -- control-plane wave batching ---------------------------------------
    # Membership/pid commands coalesce into OP_BATCH waves: each broker
    # queues the commands its own RPC handlers receive and proposes ONE
    # wave per _META_BATCH_S (early at _META_BATCH_MAX), so the metadata
    # leader's raft proposal load under a churn storm is O(brokers) per
    # wave interval instead of O(membership events). The wave apply
    # (PartitionManager.apply) defers each touched group's rebalance to
    # the end of the wave — one generation bump per group per wave —
    # and its sub-op idempotence makes a duplicate wave (leader retry
    # straddling a failover) a no-op.

    def _submit_meta(self, cmd: dict) -> bool:
        """Route one metadata command onto the wave intake. Returns
        whether the command was proposed; the caller still polls its
        own local apply for commitment, unchanged."""
        waiter = _WaveWaiter()
        cap = 4 * _META_BATCH_MAX
        with self._intake_lock:
            if len(self._intake) >= cap:
                # Bounded intake: refuse retryably instead of queueing
                # unboundedly — the client's backoff is the ladder.
                return False
            self._intake.append((cmd, waiter))
            full = len(self._intake) >= _META_BATCH_MAX
        if full:
            # A full wave needn't wait for the duty tick: the enqueuing
            # handler thread forms it inline (it would only block on the
            # waiter otherwise).
            self._drain_intake()
        waiter.event.wait(_META_BATCH_S + self.config.rpc_timeout_s * 3)
        return waiter.ok

    def _drain_intake(self) -> None:
        """Form and propose waves until the intake is empty (FIFO; at
        most _META_BATCH_MAX commands per wave). Serialized by the drain
        lock — concurrent triggers (duty tick vs a full-queue enqueue)
        must not reorder waves."""
        with self._intake_drain_lock:
            while True:
                with self._intake_lock:
                    batch = self._intake[:_META_BATCH_MAX]
                    del self._intake[: len(batch)]
                if not batch:
                    return
                self._last_wave = time.monotonic()
                cmds = [c for c, _ in batch]
                # Metadata-plane traces are op-identity rooted (no
                # client carried a ctx here): the wave ordinal seeds the
                # same deterministic sampling predicate the clients use.
                wsp = NULL_SPAN
                if self.spans is not None:
                    tid = derive_trace_id(f"wave/broker{self.broker_id}",
                                          self._wave_count)
                    if sampled(tid, self.config.trace_sample_n):
                        wsp = self.spans.span("meta.wave",
                                              TraceContext(tid, 0),
                                              {"size": len(cmds)})
                ok = self.propose_cmd({"op": OP_BATCH, "cmds": cmds})
                wsp.end(ok=ok)
                self._wave_count += 1
                self._wave_events += len(cmds)
                if not ok:
                    self._wave_failures += 1
                bucket = str(1 << (len(cmds) - 1).bit_length())
                self._wave_size_hist[bucket] = (
                    self._wave_size_hist.get(bucket, 0) + 1
                )
                self.recorder.record(
                    "meta_batch", size=len(cmds), ok=ok,
                )
                for _, w in batch:
                    w.ok = ok
                    w.event.set()

    def _batch_duty(self) -> None:
        """Wave cadence: propose the queued commands once _META_BATCH_S
        has passed since the last wave (size-triggered waves drain
        inline from the enqueuing thread, see _submit_meta)."""
        with self._intake_lock:
            pending = len(self._intake)
        if not pending:
            return
        if time.monotonic() - self._last_wave < _META_BATCH_S:
            return
        self._drain_intake()

    def _fail_pending_waves(self) -> None:
        """stop(): release every parked handler (propose refused)."""
        with self._intake_lock:
            pending = list(self._intake)
            del self._intake[:]
        for _, w in pending:
            w.ok = False
            w.event.set()

    # -- heartbeat relay ---------------------------------------------------

    def _beats_relay_duty(self) -> None:
        """Forward the locally-buffered member beats to the metadata
        leader's liveness ledger as ONE group.beats frame per relay
        interval (_HEARTBEAT_RELAY_S says how long). A frame that
        cannot be delivered (no leader, leader moved, wire error)
        re-merges into the buffer and retries next tick — the stamps
        are idempotent monotonic refreshes, and the leader-change grace
        window (GroupLiveness first-sighting seeding) absorbs delivery
        gaps exactly as it absorbs leader churn."""
        now = time.monotonic()
        if now - self._last_beat_relay < min(
                _HEARTBEAT_RELAY_S, self.config.group_session_timeout_s / 4):
            return
        with self._beat_lock:
            if not self._beat_buffer:
                return
            beats = sorted(self._beat_buffer)
            self._beat_buffer.clear()
        self._last_beat_relay = now
        delivered = False
        node = self.runner.node
        if node.role == LEADER:
            # This broker IS the ledger's owner: stamp directly.
            self._ingest_beats(beats)
            delivered = True
        else:
            hint = node.leader_hint
            if hint is not None and hint != self.broker_id:
                try:
                    resp = self._raft_client.call(
                        self._addr_of(hint),
                        {"type": "group.beats",
                         "beats": [[g, m] for g, m in beats]},
                        timeout=min(2.0, self.config.rpc_timeout_s),
                    )
                    delivered = bool(resp.get("ok"))
                except RpcError:
                    delivered = False
        if delivered:
            self._beat_frames += 1
        else:
            with self._beat_lock:
                self._beat_buffer.update(beats)
        self.recorder.record(
            "beats_relay", beats=len(beats), ok=delivered,
        )

    def _ingest_beats(self, beats) -> None:
        """Metadata leader: stamp each relayed (group, member) beat
        whose membership the replicated table confirms — per-member
        stamps preserved, evicted/unknown members dropped (their
        originating broker answers them unknown_member on the next
        heartbeat once the leave applies there)."""
        stamped = 0
        for group, member in beats:
            st = self.manager.group_state(str(group))
            if st is not None and str(member) in st.members:
                self._group_liveness.beat(str(group), str(member))
                stamped += 1
        if stamped:
            # Reached from RPC handler threads (group.beats frames) AND
            # the duty thread (the leader ingesting its own buffer):
            # the counter shares the beat-buffer leaf lock.
            with self._beat_lock:
                self._beats_relayed += stamped

    def _handle_group_beats(self, req: dict) -> dict:
        """One broker's aggregated heartbeat frame (the relay plane's
        leader-side ingestion point)."""
        node = self.runner.node
        if node.role != LEADER:
            hint = node.leader_hint
            return {"ok": False, "error": "not_leader", "leader": hint}
        self._ingest_beats(
            [(str(g), str(m)) for g, m in req.get("beats", [])]
        )
        return {"ok": True}

    # -- data path ---------------------------------------------------------

    def _check_partition(self, key, view=None
                         ) -> tuple[Optional[int], Optional[dict]]:
        """(engine slot, refusal). Unknown partitions are a TERMINAL error
        (checked before leadership, so clients don't retry nonexistent
        partitions forever); non-leadership is a retryable refusal with a
        hint — unlike the reference, which answered "Not leader" and then
        appended anyway (MessageAppendRequestProcessor.java:29-33)."""
        if view is not None:  # a multi request's part: PartitionManager.peek
            slot, leader = view[1], view[0].leader if view[0] else None
        else:
            slot = self.manager.slot_of(key)
            leader = None if slot is None else self.manager.leader_of(key)
        if slot is None:
            return None, {"ok": False, "error": f"unknown_partition: {key}"}
        if leader != self.broker_id:
            return None, {
                "ok": False,
                "error": "not_leader",
                "leader": leader,
                "leader_addr": self._addr_of(leader) if leader is not None else None,
            }
        return slot, None

    def _topic_routing(self, topic: str) -> list[dict]:
        """The topic's current assignments on the wire — what a
        `stale_partition_gen:` refusal carries so the refused client
        re-resolves routing FROM THE REFUSAL (generation, ranges,
        leaders) instead of spending a meta.topics round first."""
        for t in self.manager.get_topics():
            if t.name == topic:
                return [a.to_dict() for a in t.assignments]
        return []

    def _gen_refusal(self, req: dict, key, view=None) -> Optional[dict]:
        """Partition-generation fence (elastic partitions): a request
        stamped with `pgen` — the generation its sender resolved
        routing under — draws a typed RETRYABLE `stale_partition_gen:`
        refusal the moment a split/merge has bumped the partition's
        generation, with the topic's current assignments attached (the
        groups plane's fenced_generation discipline reapplied to
        partitions). Replicated state only, so EVERY broker fences
        identically. Unstamped requests keep the legacy contract:
        routed by partition id, with keyed writes to a splitting
        parent dual-write-forwarded instead of refused."""
        pgen = req.get("pgen")
        if pgen is None:
            return None
        if view is not None:  # a multi request's part: PartitionManager.peek
            gen = view[0].generation if view[0] else None
        else:
            gen = self.manager.generation_of(key)
        if gen is None or int(pgen) == gen:
            return None
        self._gen_fence_refusals += 1
        return {
            "ok": False,
            "error": f"stale_partition_gen: {key[0]}/{key[1]} generation "
                     f"{int(pgen)} != current {gen}",
            "generation": gen,
            "routing": self._topic_routing(key[0]),
        }

    def _retired_refusal(self, key, view=None) -> Optional[dict]:
        """Produce-side fence for a merge-retired child: its log stays
        readable for draining, but new writes must land in the parent
        that reabsorbed the range — same typed refusal + routing
        payload as the generation fence, so one client re-resolve
        handles both."""
        a = view[0] if view is not None else self.manager.assignment_of(key)
        if a is None or a.state != "retired":
            return None
        self._gen_fence_refusals += 1
        return {
            "ok": False,
            "error": f"stale_partition_gen: {key[0]}/{key[1]} is retired "
                     f"(range merged into partition {a.origin})",
            "generation": a.generation,
            "routing": self._topic_routing(key[0]),
        }

    def _handle_produce(self, req: dict) -> dict:
        """Admission + ack-latency instrumentation around the produce
        path. Admission runs FIRST — before partition resolution,
        validation or pid stamping — so a shed/quota refusal under
        overload costs one dict lookup (slo/admission.py; typed
        retryable `overloaded:`, so clients jitter-backoff instead of
        hammering the refusal). Admitted
        requests observe their full wall time (success AND failure —
        timeouts are exactly the overload signal) into `produce.ack_us`,
        the p99 the SLO controller steers against."""
        messages = req.get("messages")
        n = len(messages) if isinstance(messages, list) else 1
        # Causal tracing: a sampled produce carries `tctx` (the client
        # root span's context); rpc.recv covers this broker's whole
        # handling, admission its front-door slice. Unsampled requests
        # (no tctx, or tracing off) pay one dict-get and a None branch.
        sp = (self.spans.span("rpc.recv", ctx_from_wire(req.get("tctx")),
                              {"op": "produce"})
              if self.spans is not None else NULL_SPAN)
        asp = (self.spans.span("admission", sp.ctx)
               if sp.ctx is not None else NULL_SPAN)
        refusal = self.slo.admit(req.get("producer"), n)
        asp.end()
        if refusal is not None:
            sp.end(error="overloaded")
            return {"ok": False, "error": f"overloaded: {refusal}"}
        t0 = self.metrics.clock()
        try:
            sub = self._produce_submit(req, tctx=sp.ctx)
            return (sub if isinstance(sub, dict)
                    else self._produce_collect(*sub))
        finally:
            self._m_ack_us.observe(self.metrics.clock() - t0)
            sp.end()

    def _handle_produce_multi(self, req: dict) -> dict:
        """One produce request for MANY partitions (a keyed, batching
        producer: `ProducerClient.send`). `parts` is a list of
        {topic, partition, messages, seq, pgen, key_span}; `pid` and
        `producer` are the request's. Every part goes through the checks
        a `produce` does (quota, generation and key-range fences,
        leadership, dedup, size), ALL parts are submitted - together,
        under one hold of the plane's lock - before any is waited for,
        the request parks this one RPC worker, and the reply
        answers part by part: {"ok": true, "parts": [{"ok": true,
        "base_offset", "count"} | {"ok": false, "error", ...}]}. A part
        is acked exactly when a `produce` of it would be.

        On a leader that is not the controller the admitted parts ride
        ONE engine.append_multi frame to the controller instead of one
        engine.append each."""
        parts = req.get("parts")
        if not isinstance(parts, list) or not parts:
            return {"ok": False, "error": "bad_request: empty parts"}
        sp = NULL_SPAN
        if self.spans is not None and req.get("tctx") is not None:
            sp = self.spans.span(
                "rpc.recv", ctx_from_wire(req["tctx"]),
                {"op": "produce.multi", "parts": len(parts),
                 "msgs": sum(len(p["messages"]) for p in parts
                             if isinstance(p, dict)
                             and isinstance(p.get("messages"), list))})
        self._m_multi_requests.inc()
        self._m_multi_parts.inc(len(parts))
        t0 = self.metrics.clock()
        try:
            tpart = req.get("tpart", 0)
            batch = _AppendBatch(self.config.rpc_timeout_s)
            subs = [
                self._admit_part(part, req, batch,
                                 sp.ctx if i == tpart else None)
                for i, part in enumerate(parts)
            ]
            if batch.items:
                self._submit_batch(batch)
            self._m_multi_admit_us.observe(self.metrics.clock() - t0)
            return {"ok": True, "parts": [
                sub if isinstance(sub, dict) else self._produce_collect(*sub)
                for sub in subs
            ]}
        finally:
            self._m_ack_us.observe(self.metrics.clock() - t0)
            sp.end()

    def _admit_part(self, part, req: dict, batch, tctx):
        """One part of a produce.multi through `_produce_submit`; what a
        malformed part raises refuses that part alone."""
        try:
            messages = part["messages"]
            if not isinstance(messages, list) or not messages:
                return {"ok": False, "error": "bad_request: empty messages"}
            refusal = self.slo.admit(req.get("producer"), len(messages))
            if refusal is not None:
                return {"ok": False, "error": f"overloaded: {refusal}"}
            preq = dict(part)
            if req.get("pid") is not None and part.get("seq") is not None:
                preq["pid"] = req["pid"]
            return self._produce_submit(preq, tctx=tctx, batch=batch)
        except (KeyError, ValueError, TypeError) as e:
            return {"ok": False,
                    "error": f"bad_request: {type(e).__name__}: {e}"}
        except NotCommittedError as e:
            return {"ok": False, "error": f"not_committed: {e}"}

    def _span_refusal(self, key, span, a) -> Optional[dict]:
        """Key-range fence of a produce.multi part: the part holds many
        keys of one partition and names the span their hashes cover;
        ranges are intervals, so both ends inside means every key
        inside. A part any of whose keys has left the partition's range
        is refused whole with the generation fence's typed refusal and
        routing payload — its keys may belong to several owners now,
        which only the sender can re-split."""
        if a is None or (a.owns_key(int(span[0]))
                         and a.owns_key(int(span[1]))):
            return None
        self._gen_fence_refusals += 1
        return {
            "ok": False,
            "error": f"stale_partition_gen: {key[0]}/{key[1]} owns "
                     f"[{a.range_lo}, {a.range_hi}), the part's keys span "
                     f"[{int(span[0])}, {int(span[1])}]",
            "generation": a.generation,
            "routing": self._topic_routing(key[0]),
        }

    def _produce_submit(self, req: dict, tctx=None,
                        batch: "Optional[_AppendBatch]" = None):
        """Admit one partition batch and SUBMIT its rounds without
        waiting: a refusal dict, or (chunk sizes, waiters, routed
        partition) for `_produce_collect`. `batch` (produce.multi)
        gathers the appends of many parts for ONE submit
        (`_AppendBatch`).

        Produce semantics: at-least-once by default, EXACTLY-ONCE for
        idempotent producers. A batch larger than max_batch is split into
        pipelined rounds, and some rounds can fail while others commit (a
        failed middle round leaves a gap). ALL pipelined rounds are
        drained before responding; on any failure the error carries the
        total number of messages that did commit in `committed`, so a
        client that retries the whole batch knows it is duplicating that
        many (the reference has the same window one message at a time —
        its closure can fail after the Raft entry committed,
        MessageAppendRequestProcessor.java:36-67).

        Idempotence: a request carrying (`pid`, `seq`) — the client SDK's
        registered producer id + its ack-gated per-partition sequence —
        dedupes at the controller's append path (DataPlane.submit_append):
        a replayed sequence is acked with its original base offset, never
        appended twice, including across controller failover (the dedup
        table replicates through the settle path). A pid-less request is
        STAMPED with this broker's own pid + per-slot sequence before
        forwarding, which collapses duplicated leader→controller RPC
        frames the same way — so clean single-attempt acks are
        exactly-once for every client, opted-in or not. Chunk k of a
        split batch takes `seq + k*max_batch`-adjacent sequence ranges,
        reproducibly (max_batch is config-static), so a full-batch replay
        re-chunks identically and every chunk dedupes."""
        key = group_key(req["topic"], req["partition"])
        # A produce.multi part takes ONE lock-free look at its partition
        # (PartitionManager.peek says why); a `produce` keeps the locked
        # lookups it always made (PERF.md section 7, first item, has the
        # runs that tried it on `peek` too and why it was left).
        view = self.manager.peek(key) if batch is not None else None
        refusal = self._gen_refusal(req, key, view)
        if refusal:
            return refusal
        if view is not None and req.get("key_span") is not None:
            refusal = self._span_refusal(key, req["key_span"], view[0])
            if refusal:
                return refusal
        routed = None
        khash = req.get("key_hash")
        if khash is not None:
            owner = self.manager.route_key(req["topic"], int(khash))
            if owner is not None and owner != key[1]:
                # Elastic routing moved this key's range slice (a split
                # begun, a merge landed) and the sender has not
                # re-resolved: FORWARD the write to the current owner
                # instead of refusing — during a handoff the child's
                # leader IS the parent's, so the dual-write is a local
                # slot redirect, and the ack names the routed partition
                # (`routed_partition`) so the sender's history stays
                # attributable to the log the write actually landed in.
                key = group_key(req["topic"], owner)
                routed = owner
                view = None if view is None else self.manager.peek(key)
        refusal = self._retired_refusal(key, view)
        if refusal:
            return refusal
        slot, refusal = self._check_partition(key, view)
        if refusal:
            return refusal
        messages = req["messages"]
        if not isinstance(messages, list) or not messages:
            return {"ok": False, "error": "bad_request: empty messages"}
        B = self.config.engine.max_batch
        if req.get("pid") is not None:
            pid, seq = int(req["pid"]), int(req.get("seq", -1))
        else:
            pid, seq = self._stamp_pid_seq(slot, len(messages))
        chunks = [messages[i : i + B] for i in range(0, len(messages), B)]
        chunk_sizes = [len(c) for c in chunks]
        futs = [
            self._engine_append(
                slot, chunk, pid,
                seq + i * B if pid > 0 else -1,
                tctx=tctx, batch=batch,
            )
            for i, chunk in enumerate(chunks)
        ]
        return chunk_sizes, futs, routed

    def _produce_collect(self, chunk_sizes: list, futs: list,
                         routed: Optional[int]) -> dict:
        """Wait out one submitted batch's rounds and build its answer."""
        base0 = None
        committed = 0
        first_err: Optional[Exception] = None
        for n, fut in zip(chunk_sizes, futs):
            try:
                base = fut()
            except NotCommittedError as e:
                if first_err is None:
                    first_err = e
                continue
            if base0 is None and first_err is None:
                base0 = base
            committed += n
        if first_err is not None:
            return {"ok": False, "error": f"not_committed: {first_err}",
                    "committed": committed}
        if routed is not None:
            self._forwarded_writes += 1
            return {"ok": True, "base_offset": base0, "count": committed,
                    "routed_partition": routed}
        return {"ok": True, "base_offset": base0, "count": committed}

    def _quorum_refusal(self, slot: int, dp=_RESOLVE) -> Optional[dict]:
        """Graceful degradation: when the partition's replica quorum is
        lost (mask says no round can commit), fail FAST with a typed,
        retryable `unavailable` refusal instead of letting the request
        hang into its RPC timeout (consume's auto-commit and offset
        commits ride quorum rounds that are doomed before dispatch).
        Only the controller can see the mask; non-controller leaders get
        the same refusal from the controller's engine.* handlers. A
        request of many parts resolves the local plane once and hands
        it in as `dp`."""
        if dp is _RESOLVE:
            dp = self._local_engine()
        if dp is not None and dp.quorum_lost(slot):
            return {"ok": False,
                    "error": f"unavailable: partition slot {slot} lost "
                             f"its replica quorum (degraded; retry after "
                             f"heal)"}
        return None

    def _admit_partition(self, req: dict, key, view=None, dp=_RESOLVE
                         ) -> tuple[Optional[int], Optional[dict]]:
        """(engine slot, refusal): the admission a consume and an offset
        commit share - generation fence, existence, leadership, quorum,
        in that order. A part of a consume.multi / offset.commit.multi
        hands in its ONE lock-free look at the partition (`view`:
        PartitionManager.peek, as a produce.multi part does) and its
        request's one resolution of the local plane (`dp`); the
        single-partition requests keep the locked lookups they always
        made (PartitionManager.peek says why)."""
        refusal = self._gen_refusal(req, key, view)
        if refusal:
            return None, refusal
        slot, refusal = self._check_partition(key, view)
        if refusal:
            return None, refusal
        return slot, self._quorum_refusal(slot, dp)

    @staticmethod
    def _part_refusal(e: Exception) -> dict:
        """The answer of ONE part of a multi request whose serving
        raised `e`: what `_dispatch` answers a single-partition request
        that raises it."""
        if isinstance(e, _UpstreamRefusal):
            return dict(e.resp)
        if isinstance(e, NotCommittedError):
            return {"ok": False, "error": f"not_committed: {e}"}
        if isinstance(e, (KeyError, ValueError, TypeError)):
            return {"ok": False,
                    "error": f"bad_request: {type(e).__name__}: {e}"}
        return {"ok": False, "error": f"internal: {type(e).__name__}: {e}"}

    def _handle_consume(self, req: dict) -> dict:
        """Ack-latency instrumentation around the consume path (the
        produce.ack_us twin): every answer — leader serve, follower
        serve, refusal — observes its full wall time, less what a long
        poll stood parked, into `consume.ack_us`, the p99 the SLO
        controller's consume twin steers toward slo_p99_consume_ms (via
        read_coalesce_s)."""
        t0 = self.metrics.clock()
        self._parked_tls.s = 0.0
        sp = (self.spans.span("rpc.recv", ctx_from_wire(req.get("tctx")),
                              {"op": "consume"})
              if self.spans is not None else NULL_SPAN)
        try:
            resp = self._consume_checked(req, tctx=sp.ctx)
            if resp.get("messages"):
                self._m_fetch_answered.inc()
            return resp
        finally:
            sp.end()
            # the wall time less what a long poll stood parked
            self._m_consume_ack_us.observe(
                self.metrics.clock() - t0 - self._parked_tls.s)

    def _consume_checked(self, req: dict, tctx=None) -> dict:
        key = group_key(req["topic"], req["partition"])
        slot, refusal = self._admit_partition(req, key)
        if refusal:
            # Follower read path: a non-leader with a valid lease may
            # still answer an explicit-offset consume from its
            # replicated settled floor (client opt-in via follower_ok;
            # broker/follower.py for the safety contract). Anything it
            # cannot prove settled refuses with the retryable
            # `not_settled_here:` and the client falls back to the
            # leader named in the ordinary hint.
            if (refusal.get("error") == "not_leader"
                    and req.get("follower_ok")
                    and req.get("offset") is not None):
                answer = self._follower_consume(key, req, refusal,
                                                tctx=tctx)
                if answer is not None:
                    return answer
            return refusal
        cslot = self._resolve_consumer(req["consumer"])
        if cslot is None:
            return {"ok": False, "error": "consumer_registration_failed"}
        replica = self.manager.replica_slot(key, self.broker_id)
        if replica is None:
            replica = 0  # leader not in replicas: metadata race; read slot 0
        if req.get("offset") is not None:
            # Explicit read position (the consumer SDK's prefetch
            # pipeline): skips the committed-offset lookup; the read is
            # still leadership-checked and settled-horizon-clamped, and
            # the committed offset only moves on offset.commit.
            offset = int(req["offset"])
            if offset < 0:
                return {"ok": False, "error": "bad_request: negative offset"}
        else:
            # Read the offset from the leader's own replica slot too:
            # replica 0 may be masked dead and hold a stale offset table
            # (commits only apply on acking replicas).
            offset = self._engine_read_offset(slot, cslot, replica)
        limit = req.get("max_messages")
        msgs, next_offset = self._engine_read(
            slot, offset, replica, None if limit is None else int(limit),
            wait_s=float(req.get("wait_s", 0) or 0), tctx=tctx,
        )
        # Offsets are storage offsets (rounds are alignment-padded), so the
        # committable position is next_offset — NOT offset + len(messages).
        return {"ok": True, "messages": msgs, "offset": offset,
                "next_offset": next_offset}

    def _handle_consume_multi(self, req: dict) -> dict:
        """One consume request for MANY partitions of this leader (a
        readahead consumer's session: `ConsumerClient`). `parts` is a
        list of {topic, partition, offset, max_messages, pgen} (the last
        three optional, as in a `consume`); `consumer` is the request's
        and is resolved once. Every part goes through the admission a
        `consume` does on ONE lock-free look at its partition, the reads
        of all admitted parts are one call (`DataPlane.read_many`: one
        hold of the plane's lock for the look at every part) - or, on a
        leader that is not the controller, ONE engine.read_multi frame -
        and the reply answers part by part: {"ok": true, "parts":
        [{"ok": true, "messages", "offset", "next_offset"} | {"ok":
        false, "error", ...}]}. A part is answered as a `consume` of it
        would be. A request marked `follower_ok` that reaches a standby
        holding a current-epoch lease is served whole from its follower
        read plane instead (`_follower_fetch`: a rack-aware consumer's
        session, client/consumer.py `client_rack`). With `wait_s`
        the REQUEST long-polls: if every part was admitted and every
        read came back empty it parks once, for all its parts, and is
        answered when rows settle past the position of ANY of them, or
        at the deadline, empty (`_fetch`); a request with a refused part
        is answered at once, so that the client can take that part
        elsewhere. `consume.ack_us` observes the whole request, once,
        less what it stood parked (fetch.park_us has that)."""
        parts = req.get("parts")
        if not isinstance(parts, list) or not parts:
            return {"ok": False, "error": "bad_request: empty parts"}
        if req.get("follower_ok") and self.follower_plane is not None:
            resp = self._follower_fetch(req, parts)
            if resp is not None:
                return resp
        t0 = self.metrics.clock()
        self._parked_tls.s = 0.0
        sp = (self.spans.span("rpc.recv", ctx_from_wire(req.get("tctx")),
                              {"op": "consume.multi", "parts": len(parts)})
              if self.spans is not None else NULL_SPAN)
        self._m_cmulti_requests.inc()
        self._m_cmulti_parts.inc(len(parts))
        try:
            cslot = self._resolve_consumer(req["consumer"])
            if cslot is None:
                return {"ok": False, "error": "consumer_registration_failed"}
            dp = self._local_engine()
            answers: list = [None] * len(parts)
            items, where = [], []
            for i, part in enumerate(parts):
                try:
                    key = group_key(part["topic"], part["partition"])
                    view = self.manager.peek(key)
                    slot, refusal = self._admit_partition(part, key, view,
                                                          dp)
                    if refusal:
                        answers[i] = refusal
                        continue
                    offset, limit = part.get("offset"), part.get(
                        "max_messages")
                    if offset is not None and int(offset) < 0:
                        answers[i] = {"ok": False, "error":
                                      "bad_request: negative offset"}
                        continue
                    replicas = view[0].replicas
                    items.append((
                        slot, None if offset is None else int(offset), cslot,
                        # leader not in replicas: metadata race; slot 0
                        replicas.index(self.broker_id)
                        if self.broker_id in replicas else 0,
                        None if limit is None else int(limit)))
                    where.append(i)
                except (KeyError, ValueError, TypeError) as e:
                    answers[i] = self._part_refusal(e)
            wait_s = (float(req.get("wait_s", 0) or 0)
                      if len(items) == len(parts) else 0.0)
            rows = False
            for i, got in zip(where, self._engine_read_many(
                    items, dp, wait_s, tctx=sp.ctx)):
                if isinstance(got, tuple):
                    answers[i] = {"ok": True, "messages": got[0],
                                  "offset": got[1], "next_offset": got[2]}
                    rows = rows or bool(got[0])
                else:
                    answers[i] = self._part_refusal(got)
            if rows:
                self._m_fetch_answered.inc()
            return {"ok": True, "parts": answers}
        finally:
            sp.end()
            self._m_consume_ack_us.observe(
                self.metrics.clock() - t0 - self._parked_tls.s)

    def _follower_consume(self, key, req: dict, not_leader: dict,
                          tctx=None) -> Optional[dict]:
        """Serve a consume from the follower read plane, or None when
        this broker is not in a position to even try (feature off, no
        lease, stale generation) — the caller then answers the ordinary
        not_leader hint. The lease AND its epoch are re-checked here,
        per answer: a deposed standby drops to the hint the instant the
        handover applies, before its plane even resets."""
        fp = self.follower_plane
        if fp is None:
            return None
        slot = self.manager.slot_of(key)
        if slot is None or self._follower_epoch() is None:
            return None
        offset = int(req["offset"])
        if offset < 0:
            return {"ok": False, "error": "bad_request: negative offset"}
        limit = req.get("max_messages")
        limit = None if limit is None else int(limit)
        fsp = (self.spans.span("follower.serve", tctx, {"slot": slot})
               if self.spans is not None else NULL_SPAN)
        # A cold striped page pays a reconstruct inside fp.read —
        # attribute it (decoded-counter delta detects one) as a
        # child of follower.serve.
        dec0 = fp._decoded
        t0r = self.metrics.clock()
        got = fp.read(slot, offset, limit)
        if fsp.ctx is not None and fp._decoded > dec0:
            self.spans.span_at(
                "stripe.reconstruct", fsp.ctx, t0r,
                self.metrics.clock() - t0r,
                {"groups": fp._decoded - dec0})
        # Last-line witness: EVERY answer re-checks against the floor
        # at the boundary, independent of the serving
        # path's own fence — a failed audit refuses and is counted as
        # a first-class chaos violation (answers_past_floor).
        if got is not None and not fp.audit_answer(slot, offset, got[1]):
            got = None
        if got is None:
            fsp.end(error="not_settled_here")
            return {
                "ok": False,
                "error": f"not_settled_here: slot {slot} offset {offset} "
                         f"is above this standby's settled floor",
                "leader": not_leader.get("leader"),
                "leader_addr": not_leader.get("leader_addr"),
            }
        msgs, next_offset = got
        fsp.end(rows=len(msgs))
        return {"ok": True, "messages": msgs, "offset": offset,
                "next_offset": next_offset, "follower": True}

    def _make_follower_plane(self) -> None:
        """The follower read plane of this broker and the series of
        the requests it serves (`follower_reads` on)."""
        from ripplemq_tpu.broker.follower import FollowerReadPlane

        self.follower_plane = FollowerReadPlane(
            self.config.engine.slot_bytes,
            self.config.follower_page_cache_bytes,
            fetch_fn=(self._fetch_sibling_stripes
                      if self.config.replication == "striped" else None),
            clock=self.metrics.clock,
        )
        # A consume.multi served HERE, from the plane
        # (`_follower_fetch`): the twins of the leader's fetch.*
        # series - requests, how their parks went, requests whose
        # reply held a row, parts refused to the leader - and the
        # floor's own: stamps against frames with records, and how
        # long after the controller's settle release a pushed floor
        # reached the plane (both stamps time.monotonic_ns, so one
        # machine's). follower.serve_us is the request less its park.
        m = self.metrics
        self._m_ff_requests = m.counter("follower.fetch_requests")
        self._m_ff_parked = m.counter("follower.fetch_parked")
        self._m_ff_woken = m.counter("follower.fetch_woken")
        self._m_ff_expired = m.counter("follower.fetch_expired")
        self._m_ff_answered = m.counter("follower.fetch_answered")
        self._m_ff_refused = m.counter("follower.fetch_refused_parts")
        self._m_ff_park_us = m.histogram("follower.park_us")
        self._m_ff_wake_late_us = m.histogram("follower.wake_late_us")
        self._m_ff_serve_us = m.histogram("follower.serve_us")
        self._m_ff_floors = m.counter("follower.floors")
        self._m_ff_rounds = m.counter("follower.rounds")
        self._m_ff_floor_lag_us = m.histogram("follower.floor_lag_us")
        # Ordinals of the requests served and the pushed floors
        # received here: what a traced broker samples its own
        # follower.fetch / follower.floor roots by (a consumer that
        # does not trace sends no context to hang them on).
        self._ff_ordinal = itertools.count()
        self._floor_ordinal = itertools.count()

    def _follower_epoch(self) -> Optional[int]:
        """The controller epoch this broker may answer follower reads
        under, or None: no plane, no lease of the current epoch, or a
        plane whose bytes are another generation's. Asked per answer
        (and again after a park): a deposed standby stops serving the
        instant the handover applies, before its plane even resets."""
        fp = self.follower_plane
        if fp is None:
            return None
        epoch = self.manager.current_epoch()
        if self.manager.follower_lease(self.broker_id) != epoch:
            return None
        fp.note_epoch(epoch)  # fence the plane even before new frames
        if fp.epoch() != epoch:
            return None  # cached bytes are another generation's
        return epoch

    def _follower_park_duty(self) -> None:
        """A fetch parked on the follower plane stands on this broker's
        lease: gone (or the epoch moved, which `_follower_epoch` tells
        the plane), its reader is refused to the leader now and not at
        its deadline."""
        fp = self.follower_plane
        if (fp is not None and fp.parked()
                and self._follower_epoch() is None):
            fp.release_parks("lease")

    def _follower_fetch(self, req: dict, parts: list) -> Optional[dict]:
        """A consume.multi served by THIS standby from its follower read
        plane (Kafka's KIP-392 fetch from the in-rack replica), or None
        when it is in no position to (no lease of the current epoch;
        `replication: striped`, which decodes on read and keeps the
        single `consume`): the ordinary path then answers part by part,
        `not_leader` with the hint for every partition another broker
        leads. Every part is at an explicit offset (one without is
        refused to the leader, whose committed offset decides it) and
        takes ONE look at its partition; all reads are the plane's,
        strictly below its replicated settled floor, every answer that
        hands out rows or an advance through `audit_answer`; a part the
        plane cannot prove settled is refused with `not_settled_here:`
        and the leader's hint, its siblings served; the reply is marked
        `follower`. With `wait_s`, every part admitted and every read
        empty at the floor, the request parks ONCE for all its parts on
        the plane's waiter (`FollowerReadPlane.park`) and is answered
        when a floor stamp passes the position of ANY part - lease and
        epoch checked again before the rows go out - or at the deadline
        (clipped by `_LONG_POLL_CAP_S`), empty; an epoch change, a lost
        lease or a stop refuses the whole request. A parked request
        keeps its RPC worker, here and not on the controller."""
        from ripplemq_tpu.broker.follower import ParkRefused

        if self.config.replication == "striped":
            return None
        epoch = self._follower_epoch()
        if epoch is None:
            return None
        fp, clock = self.follower_plane, self.metrics.clock
        t0 = clock()
        ctx = ctx_from_wire(req.get("tctx"))
        if ctx is None and self.spans is not None:
            # A consumer that does not trace sent no context: every
            # trace_sample_n-th request served here roots its own.
            tid = derive_trace_id(f"follower.fetch/broker{self.broker_id}",
                                  next(self._ff_ordinal))
            if sampled(tid, self.config.trace_sample_n):
                ctx = TraceContext(tid, 0)
        sp = (self.spans.span("follower.fetch", ctx, {"parts": len(parts)})
              if self.spans is not None else NULL_SPAN)
        self._m_ff_requests.inc()
        answers: list = [None] * len(parts)
        items: list = []  # [index, slot, first offset, limit, its look]
        for i, part in enumerate(parts):
            try:
                key = group_key(part["topic"], part["partition"])
                view = self.manager.peek(key)
                offset, limit = part.get("offset"), part.get("max_messages")
                refusal = self._gen_refusal(part, key, view)
                if refusal is None and view[1] is None:
                    refusal = {"ok": False,
                               "error": f"unknown_partition: {key}"}
                if refusal is None and offset is None:
                    refusal = {"ok": False, "error": "not_leader",
                               **self._leader_hint(view)}
                if refusal is None and int(offset) < 0:
                    refusal = {"ok": False,
                               "error": "bad_request: negative offset"}
                if refusal is not None:
                    answers[i] = refusal
                    continue
                items.append([i, view[1], int(offset),
                              None if limit is None else int(limit), view])
            except (KeyError, ValueError, TypeError) as e:
                answers[i] = self._part_refusal(e)

        def read(positions: list) -> list:
            out = []
            for (_, slot, _, limit, _), pos in zip(items, positions):
                got = fp.read(slot, pos, limit, tail_ok=True)
                if (got is not None and got[1] != pos
                        and not fp.audit_answer(slot, pos, got[1])):
                    got = None  # the last-line witness (see audit_answer)
                out.append(got)
            return out

        wait_s = (float(req.get("wait_s", 0) or 0)
                  if len(items) == len(parts) else 0.0)
        deadline = time.monotonic() + min(wait_s, self._LONG_POLL_CAP_S)
        got = read([item[2] for item in items])
        outcome, parked_s, why = None, 0.0, None
        while (wait_s > 0 and got
               and all(g is not None and not g[0] for g in got)):
            left = deadline - time.monotonic()
            if left <= 0:
                outcome = "expired"
                break
            tp = clock()
            try:
                t_wake = fp.park([(item[1], g[1])
                                  for item, g in zip(items, got)],
                                 left, epoch)
            except ParkRefused as e:
                t_wake, why = None, str(e)
            tw = clock()
            parked_s += tw - tp
            if sp.ctx is not None:
                self.spans.span_at("follower.park", sp.ctx, tp, tw - tp)
            if why is None and t_wake is not None and (
                    self._stop.is_set() or self._follower_epoch() != epoch):
                why = "lease"
            if why is not None:
                break
            if t_wake is None:
                outcome = "expired"
                break
            outcome = "woken"
            # Read again, each part from where its last read ended; an
            # empty-but-advanced answer keeps its advance.
            got = read([g[1] for g in got])
            late = clock() - t_wake
            self._m_ff_wake_late_us.observe(late)
            if sp.ctx is not None:
                self.spans.span_at("follower.wake", sp.ctx, t_wake, late)
        if parked_s:
            self._m_ff_parked.inc()
            self._m_ff_park_us.observe(parked_s)
            if outcome is not None and why is None:
                (self._m_ff_woken if outcome == "woken"
                 else self._m_ff_expired).inc()
        if why is not None:
            sp.end(error=f"released: {why}")
            return {"ok": False,
                    "error": f"not_settled_here: the parked fetch was "
                             f"released ({why}); read from the leader"}
        served = refused = rows = 0
        for (i, slot, offset, _, view), g in zip(items, got):
            if g is None:
                refused += 1
                answers[i] = {
                    "ok": False,
                    "error": f"not_settled_here: slot {slot} offset "
                             f"{offset} cannot be proved settled by this "
                             f"standby",
                    **self._leader_hint(view),
                }
            else:
                answers[i] = {"ok": True, "messages": g[0],
                              "offset": offset, "next_offset": g[1]}
                if g[0]:
                    served += 1
                    rows += len(g[0])
        if served:
            self._m_ff_answered.inc()
        if refused:
            self._m_ff_refused.inc(refused)
        self._m_ff_serve_us.observe(clock() - t0 - parked_s)
        sp.end(served=served, refused=refused, rows=rows)
        return {"ok": True, "parts": answers, "follower": True}

    def _leader_hint(self, view) -> dict:
        """The `leader` / `leader_addr` of a refusal that sends its
        reader to the partition's leader (`view`: PartitionManager.peek)."""
        leader = view[0].leader if view[0] else None
        return {"leader": leader,
                "leader_addr": (self._addr_of(leader)
                                if leader is not None else None)}

    def _fetch_sibling_stripes(self, min_gsn: int) -> list:
        """FollowerReadPlane.fetch_fn (striped reconstruct-on-read):
        one page round over the live stripe holders' `stripe.fetch`,
        with a persistent forward-only cursor per peer — decode is
        sequential in gsn, so each call streams the NEXT window of
        sibling frames instead of rescanning. Returns parsed frames;
        the plane filters by epoch/gsn."""
        from ripplemq_tpu.stripes.codec import parse_frame

        holders = set(self.manager.current_stripe_map())
        live = set(self.manager.live_brokers())
        out = []
        for b in sorted(holders):
            if b == self.broker_id or b not in live:
                continue
            cur = self._follower_cursors.get(b)
            try:
                resp = self.client.call(
                    self._addr_of(b),
                    {"type": "stripe.fetch",
                     "after": -1 if cur is None else cur,
                     "min_gsn": int(min_gsn),
                     "budget": 2 << 20},
                    timeout=min(2.0, self.config.rpc_timeout_s),
                )
            except RpcError:
                continue
            if not resp.get("ok"):
                continue
            nxt = resp.get("next") or resp.get("last")
            if nxt is not None:
                self._follower_cursors[b] = list(nxt)
            for raw in resp.get("frames") or ():
                frame = parse_frame(bytes(raw))
                if frame is not None:
                    out.append(frame)
        return out

    def _handle_offset_commit(self, req: dict) -> dict:
        key = group_key(req["topic"], req["partition"])
        slot, refusal = self._admit_partition(req, key)
        if refusal:
            return refusal
        fenced = req.get("group") is not None
        if fenced:
            refusal = self._fence_group_commit(req, key)
            if refusal:
                return refusal
        cslot = self._resolve_consumer(req["consumer"])
        if cslot is None:
            return {"ok": False, "error": "consumer_registration_failed"}
        self._engine_offsets(slot, [(cslot, int(req["offset"]))])
        if fenced:
            # Re-check AFTER the offset round: the fence read (metadata
            # raft) and the offset write (engine round) are separate
            # replication planes, so a rebalance can apply between them.
            # If it did, answer FENCED even though the write landed —
            # the member then delivers nothing, which is exactly the
            # documented commit-before-deliver at-most-once outcome (a
            # crash between commit and delivery behaves identically);
            # answering ok would let a just-deposed member deliver rows
            # the partition's new owner may also deliver. The landed
            # offset itself is monotone and harmless. Residual window:
            # a rebalance applying after this re-check but before the
            # new owner's first read can still skip-or-duplicate at the
            # handover boundary — closing it fully needs the generation
            # carried INSIDE the offset round (ROADMAP, group plane).
            refusal = self._fence_group_commit(req, key)
            if refusal:
                return refusal
        return {"ok": True}

    def _handle_offset_commit_multi(self, req: dict) -> dict:
        """One offset commit for MANY partitions of this leader (the
        commit side of a readahead consumer's session). `parts` is a
        list of {topic, partition, offset, pgen}; `consumer` and - for a
        group member's commit - `group` / `member` / `generation` are
        the request's (a part may carry its own). Every part goes
        through the admission and the fences an `offset.commit` does,
        the updates of all admitted parts are submitted under ONE hold
        of the plane's lock (`DataPlane.submit_offsets_many`) - or ride
        ONE engine.offsets_multi frame to the controller - this one RPC
        worker is parked until the rounds that carry them have settled,
        and the reply answers part by part: {"ok": true, "parts":
        [{"ok": true} | {"ok": false, "error", ...}]}."""
        parts = req.get("parts")
        if not isinstance(parts, list) or not parts:
            return {"ok": False, "error": "bad_request: empty parts"}
        self._m_omulti_requests.inc()
        self._m_omulti_parts.inc(len(parts))
        cslot = self._resolve_consumer(req["consumer"])
        if cslot is None:
            return {"ok": False, "error": "consumer_registration_failed"}
        dp = self._local_engine()
        answers: list = [None] * len(parts)
        items, fences = [], []  # fences: (part index, group fields, key)
        for i, part in enumerate(parts):
            try:
                key = group_key(part["topic"], part["partition"])
                slot, refusal = self._admit_partition(
                    part, key, self.manager.peek(key), dp)
                # the request's group fields, a part's own over them
                fence = {k: src[k] for src in (req, part)
                         for k in ("group", "member", "generation")
                         if src.get(k) is not None}
                if not refusal and "group" in fence:
                    refusal = self._fence_group_commit(fence, key)
                if refusal:
                    answers[i] = refusal
                    continue
                items.append((slot, [(cslot, int(part["offset"]))]))
                fences.append((i, fence, key))
            except (KeyError, ValueError, TypeError) as e:
                answers[i] = self._part_refusal(e)
        for (i, fence, key), err in zip(fences,
                                        self._engine_offsets_many(items, dp)):
            if err is not None:
                answers[i] = self._part_refusal(err)
            else:
                # Fenced again AFTER the offset round, as an
                # `offset.commit` is (_handle_offset_commit says why).
                answers[i] = ("group" in fence and self._fence_group_commit(
                    fence, key)) or {"ok": True}
        return {"ok": True, "parts": answers}

    def _fence_group_commit(self, req: dict, key) -> Optional[dict]:
        """Generation fencing: a group commit must come from a CURRENT
        member of the CURRENT generation that OWNS the partition. A
        stale-generation member — deposed by a rebalance it has not
        observed yet — gets a typed `fenced_generation` refusal, never a
        silent overwrite of the new owner's progress (the group's
        offsets are shared state; this fence is what makes them safe
        under churn). The check reads replicated state, so ANY broker
        serving the commit fences identically."""
        group = str(req["group"])
        member = str(req.get("member", ""))
        gen = int(req.get("generation", -1))
        st = self.manager.group_state(group)
        why = None
        if st is None:
            why = f"group {group!r} does not exist"
        elif member not in st.members:
            why = f"member {member!r} is not in generation {st.generation}"
        elif gen != st.generation:
            why = f"generation {gen} != current {st.generation}"
        elif key not in st.assignment.get(member, ()):
            why = (f"partition {key} is not assigned to {member!r} in "
                   f"generation {st.generation}")
        if why is None:
            return None
        self.recorder.record(
            "fence", group=group, member=member, generation=gen,
            topic=key[0], partition=key[1],
        )
        return {"ok": False, "error": f"fenced_generation: {why}"}

    # -- elastic partitions (online split/merge) ---------------------------

    def _handle_admin_split(self, req: dict) -> dict:
        """Operator/nemesis surface: begin an online split of one
        partition. The proposal carries the parent's device-committed
        log end as the cutover WATERMARK — every write acked before
        this moment lives at or below it, and the reconfig duty gates
        the cutover on the parent's SETTLED floor crossing it (or the
        split_handoff_timeout_s bound), so the routing flip never
        strands an acked write behind an unreplicated prefix. The
        apply re-validates everything and deterministically no-ops
        when infeasible; the pre-checks here just turn the common
        no-op causes into typed answers instead of a timeout."""
        topic = str(req["topic"])
        pid = int(req["partition"])
        key = group_key(topic, pid)
        a = self.manager.assignment_of(key)
        if a is None:
            return {"ok": False, "error": f"unknown_partition: {key}"}
        if a.state != "active":
            return {"ok": False,
                    "error": f"split_infeasible: {topic}/{pid} is in "
                             f"state {a.state!r}"}
        if a.range_hi - a.range_lo < 2:
            return {"ok": False,
                    "error": f"split_infeasible: {topic}/{pid} range "
                             f"[{a.range_lo}, {a.range_hi}) is too "
                             f"narrow to split"}
        if self.manager.spare_slot_count() <= 0:
            return {"ok": False,
                    "error": "split_infeasible: no spare engine slot "
                             "(engine.partitions is a device-static "
                             "shape; splits spend pre-provisioned "
                             "spares)"}
        slot = self.manager.slot_of(key)
        try:
            watermark = self._engine_log_end(slot)
        except (RpcError, NotCommittedError) as e:
            return {"ok": False,
                    "error": f"not_committed: split watermark "
                             f"unobservable: {e}"}
        gen0 = a.generation
        if not self.propose_cmd({
            "op": OP_SPLIT_PARTITION, "topic": topic, "partition": pid,
            "watermark": int(watermark),
        }):
            return {"ok": False,
                    "error": "not_committed: split not proposed"}
        deadline = time.monotonic() + self.config.rpc_timeout_s
        while time.monotonic() < deadline:
            ho = self.manager.current_handoffs().get(key)
            if ho is not None:
                return {"ok": True, "child": int(ho["child"]),
                        "watermark": int(ho["watermark"]),
                        "generation": self.manager.generation_of(key)}
            na = self.manager.assignment_of(key)
            if na is not None and na.generation > gen0:
                # Begun AND cut over between polls: an idle parent's
                # settled floor is already at the watermark, so the
                # reconfig duty closes the window in one pass. The
                # child is the adjacent assignment this split minted.
                child = next(
                    (c.partition_id
                     for t in self.manager.get_topics() if t.name == topic
                     for c in t.assignments
                     if c.origin == pid and c.range_lo == na.range_hi),
                    None,
                )
                if child is not None:
                    return {"ok": True, "child": int(child),
                            "watermark": int(watermark),
                            "generation": na.generation}
            time.sleep(0.01)
        # Committed but no handoff window: the apply no-opped (a racing
        # split/merge changed feasibility between pre-check and apply).
        return {"ok": False,
                "error": "not_committed: split applied as a no-op "
                         "(feasibility changed in flight); re-resolve "
                         "and retry"}

    def _handle_admin_merge(self, req: dict) -> dict:
        """Reverse op: reabsorb an active split child into its parent.
        Validated against the manager's merge-candidate view (adjacent
        ranges, both active, no open handoff) — the apply re-checks the
        same conditions, so a racing proposal no-ops."""
        topic = str(req["topic"])
        parent = int(req["parent"])
        child = int(req["child"])
        if (topic, parent, child) not in self.manager.merge_candidates():
            return {"ok": False,
                    "error": f"merge_infeasible: {topic}/{parent}+"
                             f"{child} is not an adjacent active "
                             f"split pair"}
        if not self.propose_cmd({
            "op": OP_MERGE_PARTITIONS, "topic": topic,
            "parent": parent, "child": child,
        }):
            return {"ok": False,
                    "error": "not_committed: merge not proposed"}
        deadline = time.monotonic() + self.config.rpc_timeout_s
        while time.monotonic() < deadline:
            ca = self.manager.assignment_of(group_key(topic, child))
            if ca is not None and ca.state == "retired":
                return {"ok": True,
                        "generation": self.manager.generation_of(
                            group_key(topic, parent))}
            time.sleep(0.01)
        return {"ok": False,
                "error": "not_committed: merge applied as a no-op "
                         "(pair no longer mergeable); re-resolve and "
                         "retry"}

    # -- producers / groups ------------------------------------------------

    def _handle_producer_register(self, req: dict) -> dict:
        """Issue (or look up) a producer id: proposes the replicated
        registration and waits for the local apply — the same shape as
        consumer registration, minus the slot table (pids are a counter,
        not a fixed device dimension)."""
        name = str(req["name"])
        pid = self.manager.producer_id(name)
        if pid is not None:
            return {"ok": True, "pid": pid}
        if not self._submit_meta(
            {"op": OP_REGISTER_PRODUCER, "producer": name}
        ):
            return {"ok": False, "error": "not_committed: producer "
                                          "registration not proposed"}
        deadline = time.monotonic() + self.config.rpc_timeout_s
        while time.monotonic() < deadline:
            pid = self.manager.producer_id(name)
            if pid is not None:
                return {"ok": True, "pid": pid}
            time.sleep(0.01)
        return {"ok": False, "error": "not_committed: producer "
                                      "registration timed out"}

    def _handle_group(self, t: str, req: dict) -> dict:
        if t == "group.beats":
            # The relay plane's aggregated frame (no single `group`).
            return self._handle_group_beats(req)
        group = str(req["group"])
        if t == "group.describe":
            st = self.manager.group_state(group)
            if st is None:
                return {"ok": True, "exists": False, "generation": -1,
                        "members": [], "assignment": {}}
            return {
                "ok": True, "exists": True, "generation": st.generation,
                "members": sorted(st.members),
                "assignment": {
                    m: [[tp, p] for tp, p in keys]
                    for m, keys in st.assignment.items()
                },
            }
        member = str(req["member"])
        if t == "group.join":
            topics = [str(x) for x in req.get("topics", [])]
            known = {tp.name for tp in self.config.topics}
            bad = [x for x in topics if x not in known]
            if not topics or bad:
                return {"ok": False,
                        "error": f"bad_request: unknown topics {bad}"}
            st = self.manager.group_state(group)
            if (st is None or st.members.get(member)
                    != tuple(sorted(set(topics)))):
                if not self._submit_meta({
                    "op": OP_GROUP_JOIN, "group": group, "member": member,
                    "topics": topics,
                }):
                    return {"ok": False,
                            "error": "not_committed: join not proposed"}
            deadline = time.monotonic() + self.config.rpc_timeout_s
            while time.monotonic() < deadline:
                st = self.manager.group_state(group)
                if st is not None and member in st.members:
                    return self._member_view(st, member)
                time.sleep(0.01)
            return {"ok": False, "error": "not_committed: join timed out"}
        if t == "group.leave":
            st = self.manager.group_state(group)
            if st is None or member not in st.members:
                return {"ok": True}  # idempotent
            if not self._submit_meta({
                "op": OP_GROUP_LEAVE, "group": group, "member": member,
                "reason": str(req.get("reason", "leave")),
            }):
                return {"ok": False,
                        "error": "not_committed: leave not proposed"}
            deadline = time.monotonic() + self.config.rpc_timeout_s
            while time.monotonic() < deadline:
                st = self.manager.group_state(group)
                if st is None or member not in st.members:
                    return {"ok": True}
                time.sleep(0.01)
            return {"ok": False, "error": "not_committed: leave timed out"}
        if t == "group.heartbeat":
            # Answered LOCALLY: membership/generation/assignment are
            # replicated state, identical on every broker, so the
            # member's view needs no leader round trip. The liveness
            # stamp — which IS the metadata leader's ledger — is
            # buffered and rides this broker's next group.beats frame
            # (_beats_relay_duty): leader heartbeat RPC load collapses
            # from O(members) to O(brokers). A member this broker's
            # replicated view does not (yet) hold gets the same
            # unknown_member refusal the leader gave — a lagging view
            # heals by the member's transparent rejoin, an eviction by
            # the same path as before.
            st = self.manager.group_state(group)
            if st is None or member not in st.members:
                return {"ok": False,
                        "error": f"unknown_member: {member!r} not in "
                                 f"{group!r} (evicted or never joined); "
                                 f"rejoin required"}
            with self._beat_lock:
                self._beat_buffer.add((group, member))
            self._heartbeats_local += 1
            return self._member_view(st, member)
        return {"ok": False, "error": f"unknown request type {t!r}"}

    def _member_view(self, st, member: str) -> dict:
        return {
            "ok": True,
            "generation": st.generation,
            "members": sorted(st.members),
            "assignment": [
                [tp, p] for tp, p in st.assignment.get(member, ())
            ],
        }

    def _resolve_consumer(self, consumer: str) -> Optional[int]:
        """Consumer name → replicated slot, registering on first sight.

        The reference keys offsets by raw consumerId strings inside each
        partition state machine (PartitionStateMachine.java:27); here the
        name→slot binding is cluster metadata and the device table is
        int-indexed."""
        slot = self.manager.consumer_slot(consumer)
        if slot is not None:
            return slot
        cmd = {
            "op": OP_REGISTER_CONSUMER,
            "consumer": consumer,
            "slot": self.manager.next_consumer_slot(),
        }
        if not self.propose_cmd(cmd):
            return None
        deadline = time.monotonic() + self.config.rpc_timeout_s
        while time.monotonic() < deadline:
            slot = self.manager.consumer_slot(consumer)
            if slot is not None:
                return slot
            time.sleep(0.01)
        # Concurrent registrations can fill the table between this
        # broker's pre-proposal slot pick and the replicated apply, which
        # then drops the command (manager._apply_register_consumer); probe
        # fullness so that race surfaces as the same typed refusal as the
        # pre-proposal check instead of a generic registration timeout.
        # Re-check the name on BOTH sides of the probe: its own apply may
        # land just past the poll deadline (even filling the table), and
        # a successful registration must never surface as the permanent,
        # non-retryable refusal.
        slot = self.manager.consumer_slot(consumer)
        if slot is not None:
            return slot
        try:
            self.manager.next_consumer_slot()
        except ConsumerTableFullError:
            slot = self.manager.consumer_slot(consumer)
            if slot is not None:
                return slot
            raise
        return None

    # -- engine access (direct on the controller, RPC from peers) ---------

    def _controller_addr(self) -> str:
        return self._addr_of(self.manager.current_controller())

    def _engine_call(self, req: dict) -> dict:
        resp = self.client.call(
            self._controller_addr(), req, timeout=self.config.rpc_timeout_s
        )
        if not resp.get("ok"):
            err = str(resp.get("error", ""))
            if err.startswith("unavailable:"):
                # Typed degradation refusal (quorum lost): pass it to
                # the client verbatim — a non-controller leader must
                # surface the same `unavailable:` prefix the controller
                # serves directly (_quorum_refusal).
                raise _UpstreamRefusal(resp)
            if "not_committed" in err or "not_controller" in err:
                # not_controller is TRANSIENT (controller booting after
                # restart — gated on metadata freshness — or moving):
                # surface the same retryable refusal as an uncommitted
                # round, not an opaque internal RpcError.
                raise NotCommittedError(err)
            raise RpcError(f"engine call failed: {err}")
        return resp

    def _stamp_pid_seq(self, slot: int, n: int) -> tuple[int, int]:
        """Broker-side idempotence stamp for a pid-less produce: this
        broker's own pid (once its registration applied — see the duty)
        plus `n` sequence numbers from the per-slot counter. (0, -1)
        while the pid is still registering: the produce flows unstamped
        rather than stall behind the metadata raft."""
        # The pid adopt and the sequence stamp share ONE critical
        # section (_stamp_lock): the duty's reap-adoption also writes
        # _broker_pid, and an unguarded lazy write here could stamp a
        # sequence against a pid the duty was swapping out from under
        # it (ownership lint, PR 11 — the stamp and its pid must be one
        # consistent pair).
        with self._stamp_lock:
            pid = self._broker_pid
            if pid is None:
                pid = self.manager.producer_id(self._broker_pid_name)
                if pid is None:
                    return 0, -1
                self._broker_pid = pid
            seq = self._stamp_seqs.get(slot, 0)
            self._stamp_seqs[slot] = seq + n
        return pid, seq

    def _producer_pid_duty(self) -> None:
        """Register this broker's stamping pid with the metadata plane
        (once; re-proposed at 1 s spacing until the apply lands). The
        name embeds a per-boot nonce, so a restarted broker gets a FRESH
        pid — its in-memory sequence counters restart at zero, and
        reusing the old pid would collide with the table the cluster
        still holds for it. A registered pid then RE-REGISTERS at a
        third of pid_retention_s: the registration apply bumps the
        replicated seen counter, which is the session refresh the
        pid reaper keys on — a live broker's stamping pid never
        expires."""
        now = time.monotonic()
        cur = self.manager.producer_id(self._broker_pid_name)
        if cur is not None and cur != self._broker_pid:
            # ADOPT whatever pid the registry holds for our name: if the
            # old pid was reaped while this broker was partitioned past
            # the retention window, the refresh below re-registered the
            # name under a FRESH pid — stamping must move to it, or
            # every stamp would ride a reaped pid whose dedup entries
            # the reconciler deletes each tick (a silent duplicate
            # window on the forwarded hop). Sequence counters carry
            # over safely: the fresh pid's table is empty, so every
            # current counter value is above its settled end. Adopted
            # under _stamp_lock — the stamping path reads pid + seq as
            # one pair under the same lock (ownership lint, PR 11).
            with self._stamp_lock:
                self._broker_pid = cur
        if cur is not None:
            retention = self.config.pid_retention_s
            if retention <= 0:
                return
            if now - self._broker_pid_refreshed < max(1.0, retention / 3):
                return
            self._broker_pid_refreshed = now
            self.propose_cmd(
                {"op": OP_REGISTER_PRODUCER,
                 "producer": self._broker_pid_name},
                retries=1,
            )
            return
        if now - self._broker_pid_proposed < 1.0:
            return
        self._broker_pid_proposed = now
        self._broker_pid_refreshed = now
        self.propose_cmd(
            {"op": OP_REGISTER_PRODUCER, "producer": self._broker_pid_name},
            retries=1,
        )

    def _pid_reap_duty(self) -> None:
        """Producer-id expiry (the PR 7 grow-forever residual closed):
        pids get sessions like groups got. The metadata LEADER stamps
        each pid's replicated seen counter into a volatile per-tenure
        ledger; a pid whose counter has not moved for pid_retention_s
        is reaped via OP_RETIRE_PRODUCER — whose apply re-checks the
        counter, so a racing re-registration (ProducerClient refreshes
        at pid_refresh_s; the broker stamping pid at retention/3)
        always wins. The CONTROLLER side reconciles its dedup table
        against the registry on the same cadence: boot replay rebuilds
        REC_PIDSEQ entries for pids reaped while it was down, and
        those must not linger (admin.stats `pid_table_size` stops
        growing monotonically under client churn — the directed test's
        assertion)."""
        retention = self.config.pid_retention_s
        if retention <= 0:
            return
        now = time.monotonic()
        # Controller-side reconciliation (any broker with the plane).
        dp = self._local_engine()
        if dp is not None and now - self._last_pid_reconcile >= max(
            1.0, min(5.0, retention / 4)
        ):
            self._last_pid_reconcile = now
            keep, next_pid = self.manager.registered_pids()
            dp.retain_pids(keep | {0}, below=next_pid)
        node = self.runner.node
        if node.role != LEADER:
            # Stamps from a previous tenure are stale the moment the
            # lease moves (the group-liveness rule): clear, so a fresh
            # leader grants every pid a full retention window.
            self._pid_seen_at.clear()
            return
        sessions = self.manager.producer_sessions()
        for name in list(self._pid_seen_at):
            if name not in sessions:
                del self._pid_seen_at[name]
        for name, (pid, seen) in sessions.items():
            prev = self._pid_seen_at.get(name)
            if prev is None or prev[0] != seen:
                self._pid_seen_at[name] = (seen, now)
                continue
            if now - prev[1] > retention:
                self._pid_seen_at.pop(name, None)
                log.info("broker %d: reaping idle producer id %d (%s)",
                         self.broker_id, pid, name)
                self.propose_cmd(
                    {"op": OP_RETIRE_PRODUCER, "producer": name,
                     "seen": seen},
                    retries=1,
                )

    def _engine_append(self, slot: int, messages: list[bytes],
                       pid: int = 0, seq: int = -1, tctx=None,
                       batch: "Optional[_AppendBatch]" = None
                       ) -> Callable[[], int]:
        """Returns a waiter so multi-chunk produces pipeline their rounds
        (both paths submit WITHOUT blocking: local futures, or pipelined
        RPC frames when a TcpClient with call_async is underneath).
        `tctx` (a sampled produce's TraceContext) rides into the local
        plane's pending entry — the settle release emits the six stage
        spans under it — or onto the forwarded engine.append frame for
        the controller to do the same."""
        if batch is not None:
            return batch.add(slot, messages, pid, seq, tctx)
        dp = self._local_engine()
        if dp is not None:
            fut = dp.submit_append(slot, messages, pid=pid, seq=seq,
                                   tctx=tctx)
            return lambda: int(fut.result(timeout=self.config.rpc_timeout_s))
        req = {"type": "engine.append", "slot": slot, "messages": messages,
               "pid": pid, "seq": seq}
        if tctx is not None:
            req["tctx"] = tctx.wire()
        call_async = getattr(self.client, "call_async", None)
        if call_async is None:  # in-proc transport: synchronous by design
            resp = self._engine_call(req)
            return lambda: int(resp["base_offset"])
        rpc_fut = call_async(self._controller_addr(), req)

        def wait() -> int:
            resp = rpc_fut.result(timeout=self.config.rpc_timeout_s)
            if not resp.get("ok"):
                if "not_committed" in str(resp.get("error", "")):
                    raise NotCommittedError(resp["error"])
                raise RpcError(f"engine call failed: {resp.get('error')}")
            return int(resp["base_offset"])

        return wait

    def _submit_batch(self, batch: "_AppendBatch") -> None:
        """Submit every append of one produce.multi and fill in
        `batch.results`: futures from the local plane, or — forwarded to
        the controller in ONE frame — base offsets. A refused or failed
        frame fails each item as an uncommitted round (retryable): the
        sender's (pid, seq) make the retry safe."""
        dp = self._local_engine()
        if dp is not None:
            batch.results = dp.submit_appends(batch.items)
            return
        req = {"type": "engine.append_multi",
               "items": [list(item[:4]) for item in batch.items]}
        for i, item in enumerate(batch.items):
            if item[4] is not None:  # the one sampled part's context
                req["tctx"], req["titem"] = item[4].wire(), i
        try:
            resp = self.client.call(self._controller_addr(), req,
                                    timeout=self.config.rpc_timeout_s)
            if not resp.get("ok"):
                raise RpcError(str(resp.get("error")))
            results = [
                int(r) if isinstance(r, int)
                else NotCommittedError(str(r.get("error")))
                for r in resp["results"]
            ]
            if len(results) != len(batch.items):
                raise RpcError("engine.append_multi answered "
                               f"{len(results)} of {len(batch.items)} items")
        except (RpcError, KeyError, TypeError, AttributeError) as e:
            results = [NotCommittedError(f"forward to controller: {e}")
                       ] * len(batch.items)
        batch.results = results

    def _read_barrier(self) -> None:
        """linearizable_reads: confirm this broker still commands the
        current controller epoch before serving committed data (off by
        default — see ClusterConfig.linearizable_reads for semantics
        and cost)."""
        if not self.config.linearizable_reads:
            return
        self._barrier_gate.wait(
            timeout_s=min(5.0, self.config.rpc_timeout_s)
        )

    def _fire_read_barrier(self) -> None:
        rep = self._replicator
        if rep is None:
            # No standby stream configured (standby_count 0): controller
            # failover is disabled, so no newer epoch can exist to fence
            # against — the local engine is trivially current.
            return
        rep.replicate([], timeout_s=min(2.0, self.config.rpc_timeout_s))

    # Long-poll ceiling: a parked fetch keeps its RPC worker, so the
    # server-side wait is clipped well below any client RPC timeout (and
    # the worker pool size bounds how many can park at once: one per
    # consumer and leader with a session, one per partition without).
    _LONG_POLL_CAP_S = 10.0

    def _fetch(self, dp, items: list, wait_s: float, read, tctx=None,
               count: bool = True) -> tuple[list, Optional[list]]:
        """The reads of one consume or consume.multi against the local
        plane `dp`, long-polling for `wait_s`: `read(items)` answers as
        `DataPlane.read_many` does ((messages, offset, next_offset) or
        an exception per (slot, offset, consumer slot, replica, limit)).
        While EVERY answer is empty the request parks on the plane
        (`DataPlane.park`: one stand for all its parts, on an event the
        settle thread's release sets - no tick, no lock take while
        nothing settles) and reads again, from where each part's last
        read ended, when rows settle past ANY of those positions, so it
        answers only what a consume at that moment would: settled,
        commit-bounded rows. An empty-but-advanced answer (below a
        settled gap or an all-padding tail) parks BEHIND its advance and
        still hands the advance back; each stand waits past the horizon
        seen before the read it follows, so a read that came back empty
        under a horizon already past its position cannot spin. The read
        barrier was the caller's: rows arriving during the wait are
        NEWER than its proof, never staler. A plane that stops or a
        controller deposed under a park refuses it (NotCommittedError).
        Returns the answers and how the parking went: None, or
        [outcome ("woken", "expired"), seconds parked] - counted here
        unless the leader that forwarded the request does (`count`)."""
        if wait_s <= 0:
            return read(items), None
        slots = [item[0] for item in items]
        ends = dp.horizons(slots)
        out = read(items)
        deadline = time.monotonic() + min(wait_s, self._LONG_POLL_CAP_S)
        clock = self.metrics.clock
        outcome, parked_s = None, 0.0
        while out and all(isinstance(r, tuple) and not r[0] for r in out):
            left = deadline - time.monotonic()
            if left <= 0:
                outcome = "expired"
                break
            t0 = clock()
            t_wake = dp.park(
                [(slot, max(r[2], end))
                 for slot, r, end in zip(slots, out, ends)], left)
            t1 = clock()
            parked_s += t1 - t0
            if tctx is not None and self.spans is not None:
                self.spans.span_at("fetch.park", tctx, t0, t1 - t0)
            if t_wake is None:
                outcome = "expired"
                break
            outcome = "woken"
            if self._stop.is_set() or self._local_engine() is not dp:
                raise NotCommittedError(
                    "controller deposed under a parked fetch")
            ends = dp.horizons(slots)
            again = read([(item[0], r[2], *item[2:])
                          for item, r in zip(items, out)])
            out = [(g[0], r[1], g[2]) if isinstance(g, tuple) else g
                   for r, g in zip(out, again)]
            self._m_wake_late_us.observe(clock() - t_wake)
        park = None if outcome is None else [outcome, parked_s]
        if count:
            self._note_park(park)  # its span is `_fetch`'s own, above
        return out, park

    def _note_park(self, park, tctx=None) -> None:
        """Count how one request's parking went (`_fetch`'s second
        answer, or the `park` of a forwarded read's reply). With `tctx`
        the park ran on the controller: its length becomes the
        fetch.park span under this broker's rpc.recv, ending now."""
        if not park:
            return
        self._m_fetch_parked.inc()
        (self._m_fetch_woken if park[0] == "woken"
         else self._m_fetch_expired).inc()
        dur = float(park[1])
        self._parked_tls.s = getattr(self._parked_tls, "s", 0.0) + dur
        if tctx is not None and self.spans is not None:
            self.spans.span_at("fetch.park", tctx,
                               self.metrics.clock() - dur, dur)

    def _forward_wait(self, wait_s: float) -> float:
        """A forwarded wait must finish inside the engine-call RPC
        timeout or the long poll would read as a dead controller."""
        return min(wait_s, max(0.0, self.config.rpc_timeout_s - 1))

    def _read_local(self, dp, slot: int, offset: int, replica: int,
                    max_msgs: Optional[int], wait_s: float, tctx=None,
                    count: bool = True):
        """One partition's read against the local plane, long-polling
        for `wait_s` (`_fetch`): (messages, next_offset, how the parking
        went)."""
        self._read_barrier()

        def read(items: list) -> list:
            (_, offset, _, _, _), = items
            msgs, end = dp.read(slot, offset, replica, max_msgs)
            return [(msgs, offset, end)]

        # Long-poll: an empty fetch parks in `_fetch` until rows settle
        # past `offset` or the window lapses, so a tail consumer costs
        # one RPC per DELIVERY instead of one per poll.
        (got,), park = self._fetch(
            dp, [(slot, offset, 0, replica, max_msgs)], wait_s, read,
            tctx=tctx, count=count)
        return got[0], got[2], park

    def _engine_read(self, slot: int, offset: int, replica: int,
                     max_msgs: Optional[int] = None,
                     wait_s: float = 0.0, tctx=None):
        dp = self._local_engine()
        if dp is not None:
            return self._read_local(dp, slot, offset, replica, max_msgs,
                                    wait_s, tctx)[:2]
        resp = self._engine_call(
            {"type": "engine.read", "slot": slot, "offset": offset,
             "replica": replica, "max_msgs": max_msgs,
             "wait_s": self._forward_wait(wait_s)})
        self._note_park(resp.get("park"), tctx)
        return list(resp["messages"]), int(resp["end"])

    def _engine_call_items(self, t: str, items: list, decode,
                           wait_s: float = 0.0, tctx=None) -> list:
        """ONE engine.*_multi frame to the controller for the items of a
        multi request: per item `decode` of its result - a value, or the
        exception that refuses it. A refused or failed frame fails every
        item. `wait_s` rides a read's frame (`_forward_wait`)."""
        try:
            req = {"type": t, "items": items}
            if wait_s > 0:
                req["wait_s"] = self._forward_wait(wait_s)
            resp = self._engine_call(req)
            self._note_park(resp.get("park"), tctx)
            results = [decode(r) for r in resp["results"]]
            if len(results) != len(items):
                raise RpcError(f"{t} answered {len(results)} of "
                               f"{len(items)} items")
            return results
        except (RpcError, NotCommittedError, _UpstreamRefusal, KeyError,
                TypeError, AttributeError) as e:
            return [e] * len(items)

    def _engine_read_many(self, items: list, dp, wait_s: float = 0.0,
                          tctx=None) -> list:
        """The reads of one consume.multi, as `DataPlane.read_many`
        takes and answers them: from the local plane `dp`, or forwarded
        to the controller in ONE engine.read_multi frame, never part by
        part; long-polling for `wait_s`, here or there (`_fetch`)."""
        if not items:
            return []
        if dp is not None:
            self._read_barrier()
            try:
                return self._fetch(dp, items, wait_s, dp.read_many,
                                   tctx=tctx)[0]
            except NotCommittedError as e:  # released under its park
                return [e] * len(items)
        return self._engine_call_items(
            "engine.read_multi", [list(item) for item in items],
            lambda r: (list(r[0]), int(r[1]), int(r[2]))
            if isinstance(r, list)
            else NotCommittedError(str(r.get("error"))),
            wait_s, tctx)

    def _engine_log_end(self, slot: int) -> int:
        """The slot's device-committed absolute log end, from the local
        plane or the controller's (the split watermark observation —
        admin.split can be served by any broker)."""
        dp = self._local_engine()
        if dp is not None:
            return dp.log_end(slot)
        resp = self._engine_call({"type": "engine.log_end", "slot": slot})
        return int(resp["end"])

    def _engine_read_offset(self, slot: int, cslot: int, replica: int = 0) -> int:
        dp = self._local_engine()
        if dp is not None:
            return dp.read_offset(slot, cslot, replica)
        resp = self._engine_call(
            {"type": "engine.read_offset", "slot": slot, "cslot": cslot,
             "replica": replica}
        )
        return int(resp["offset"])

    def _engine_offsets(self, slot: int, updates: list[tuple[int, int]]) -> None:
        dp = self._local_engine()
        if dp is not None:
            dp.submit_offsets(slot, updates).result(
                timeout=self.config.rpc_timeout_s
            )
            return
        self._engine_call(
            {"type": "engine.offsets", "slot": slot,
             "updates": [[s, o] for s, o in updates]}
        )

    def _engine_offsets_many(self, items: list, dp) -> list:
        """`_engine_offsets` for the parts of one offset.commit.multi,
        as (slot, updates): every item submitted before any is waited
        for - under one hold of the local plane `dp`'s lock, or in ONE
        engine.offsets_multi frame to the controller. Per item None
        (settled) or the exception that failed it."""
        if not items:
            return []
        if dp is not None:
            results: list = []
            for fut in dp.submit_offsets_many(items):
                try:
                    fut.result(timeout=self.config.rpc_timeout_s)
                    results.append(None)
                except Exception as e:
                    results.append(e)
            return results
        return self._engine_call_items(
            "engine.offsets_multi",
            [[slot, [[s, o] for s, o in updates]] for slot, updates in items],
            lambda r: None if r is True
            else _UpstreamRefusal(r) if str(r.get("error", "")).startswith(
                "unavailable:")
            else NotCommittedError(str(r.get("error"))))

    def _handle_engine(self, t: str, req: dict) -> dict:
        dp = self._local_engine()
        if dp is None:
            return {"ok": False, "error": "not_controller",
                    "controller_addr": self._controller_addr()}
        if t == "engine.append":
            # Forwarded append from a non-controller leader: a sampled
            # produce's tctx rode the frame — the controller's rpc.recv
            # span closes the leader→controller cross-process edge and
            # parents the engine stage spans (settle release emits them
            # under the pending entry's tctx).
            sp = (self.spans.span("rpc.recv", ctx_from_wire(req.get("tctx")),
                                  {"op": t})
                  if self.spans is not None else NULL_SPAN)
            try:
                fut = dp.submit_append(
                    int(req["slot"]), list(req["messages"]),
                    pid=int(req.get("pid", 0) or 0),
                    seq=int(req.get("seq", -1)
                            if req.get("seq") is not None else -1),
                    tctx=sp.ctx,
                )
                return {"ok": True, "base_offset":
                        int(fut.result(self.config.rpc_timeout_s))}
            finally:
                sp.end()
        if t == "engine.append_multi":
            # The forwarded appends of one produce.multi: submit every
            # item, then wait for each; one answer per item (its base
            # offset, or {"error"}), so a failed round fails its item
            # alone.
            items = req["items"]
            sp = (self.spans.span("rpc.recv", ctx_from_wire(req.get("tctx")),
                                  {"op": t, "parts": len(items)})
                  if self.spans is not None else NULL_SPAN)
            try:
                titem = req.get("titem", 0)
                futs = dp.submit_appends([
                    (int(slot), list(messages), int(pid or 0),
                     int(seq) if seq is not None else -1,
                     sp.ctx if i == titem else None)
                    for i, (slot, messages, pid, seq) in enumerate(items)
                ])
                results: list = []
                for fut in futs:
                    try:
                        results.append(
                            int(fut.result(self.config.rpc_timeout_s)))
                    except Exception as e:
                        results.append(
                            {"error": f"{type(e).__name__}: {e}"})
                return {"ok": True, "results": results}
            finally:
                sp.end()
        if t == "engine.read":
            limit = req.get("max_msgs")
            # A forwarded long poll parks here, on the plane; the
            # leader that took the client's request counts how it went.
            msgs, end, park = self._read_local(
                dp, int(req["slot"]), int(req["offset"]),
                int(req["replica"]), None if limit is None else int(limit),
                float(req.get("wait_s", 0) or 0), count=False)
            return {"ok": True, "messages": msgs, "end": end,
                    **({"park": park} if park else {})}
        if t == "engine.read_multi":
            # The forwarded reads of one consume.multi: one answer per
            # item ([messages, offset, next_offset], or {"error"}).
            self._read_barrier()
            out, park = self._fetch(dp, [
                (int(slot), None if offset is None else int(offset),
                 int(cslot), int(replica),
                 None if limit is None else int(limit))
                for slot, offset, cslot, replica, limit in req["items"]
            ], float(req.get("wait_s", 0) or 0), dp.read_many, count=False)
            return {"ok": True, "results": [
                list(r) if isinstance(r, tuple)
                else {"error": f"{type(r).__name__}: {r}"}
                for r in out
            ], **({"park": park} if park else {})}
        if t == "engine.read_offset":
            return {"ok": True, "offset": dp.read_offset(
                int(req["slot"]), int(req["cslot"]),
                int(req.get("replica", 0)))}
        if t == "engine.log_end":
            return {"ok": True, "end": dp.log_end(int(req["slot"]))}
        if t == "engine.offsets":
            refusal = self._quorum_refusal(int(req["slot"]))
            if refusal:
                return refusal
            fut = dp.submit_offsets(
                int(req["slot"]), [(int(s), int(o)) for s, o in req["updates"]]
            )
            fut.result(self.config.rpc_timeout_s)
            return {"ok": True}
        if t == "engine.offsets_multi":
            # The forwarded updates of one offset.commit.multi: the
            # quorum refusal per item, one submit for the rest, one
            # answer per item (true, or {"error"}).
            items = [(int(slot), [(int(s), int(o)) for s, o in updates])
                     for slot, updates in req["items"]]
            results: list = [self._quorum_refusal(slot, dp)
                             for slot, _ in items]
            live = [i for i, r in enumerate(results) if r is None]
            for i, err in zip(live, self._engine_offsets_many(
                    [items[i] for i in live], dp)):
                results[i] = True if err is None else {
                    "error": f"{type(err).__name__}: {err}"}
            return {"ok": True, "results": results}
        return {"ok": False, "error": f"unknown engine op {t!r}"}

    def _handle_repl_rounds(self, req: dict) -> dict:
        """Standby side of committed-round replication
        (broker/replication.py). Epoch-fenced: rejecting a stale epoch is
        what deposes an old controller — its resolver fails the round
        with FencedError and producers re-route."""
        epoch = int(req["epoch"])
        # Both refusals from ONE fence view of this broker's manager (no
        # lock: that lock also admits the produces and serves the
        # consumes of the partitions this broker leads).
        view = self.manager.fence_view
        if epoch < view.epoch:
            return {"ok": False, "error": "stale_epoch", "epoch": view.epoch}
        if self.dataplane is not None and view.controller == self.broker_id:
            # Our metadata lags a newer epoch (or a deposed peer streams
            # at ours): refuse non-fatally; the sender retries until the
            # fence duty on one side resolves it.
            return {"ok": False, "error": "active_controller"}
        if self._store_quarantined and not self._quarantine_left_set:
            # This broker's store was quarantined (reopened EMPTY) while
            # the replicated metadata still lists it as a standby from
            # BEFORE it died. Acking live rounds now would keep that
            # stale membership looking healthy — and a later promotion
            # would serve the suffix-only store as the full history
            # (observed in the proc disk-fault drills as a total acked-
            # history reset). Refuse until the controller prunes us from
            # the set (the sender flags us suspect on this error) and
            # re-admits via the full catch-up stream.
            return {"ok": False, "error": "store_quarantined"}
        store = self._round_store
        if store is None:
            return {"ok": False, "error": "no_store"}
        sseq = req.get("sseq")
        gate_key = None
        if sseq is not None:
            # Pipelined stream: apply strictly in per-stream sequence
            # order (see _ReplStreamGate — duplicates re-apply, gaps
            # refuse with the expected counter so the sender rewinds).
            sseq = int(sseq)
            gate_key = (int(req.get("sender", -1)), epoch)
            if not self._repl_gate.enter(gate_key, sseq):
                return {"ok": False,
                        "error": "repl_seq_gap: pipelined predecessor "
                                 "frame missing; rewind onto expected",
                        "expected": self._repl_gate.expected(gate_key)}
        recs = [(int(t), int(s), int(b), p) for t, s, b, p in req["records"]]
        # Standby-side apply spans: one repl.apply per sampled produce
        # whose tctx rode the frame — the cross-process child the
        # assembler pairs with the sender's repl.send for this edge's
        # clock-skew estimate.
        sps = ([self.spans.span("repl.apply", ctx_from_wire(raw),
                                {"records": len(recs)})
                for raw in req.get("tctx", ())]
               if self.spans is not None else ())
        append_many = getattr(store, "append_many", None)
        if append_many is not None:
            append_many(recs)  # one batched write per frame (group commit)
        else:
            for rec in recs:
                store.append(*rec)
        if gate_key is not None:
            self._repl_gate.applied(gate_key, sseq)
        for s in sps:
            s.end()
        fp = self.follower_plane
        if fp is not None:
            # Feed the follower read plane: this frame's rows plus the
            # leader's piggybacked floor stamp (missing on frames from
            # pre-floor senders — the plane then holds rows it cannot
            # yet serve, which is the safe direction).
            fp.ingest_rounds(epoch, recs, req.get("floors"))
            if recs:
                self._m_ff_rounds.inc()
            if req.get("floors"):
                self._m_ff_floors.inc()
            if req.get("floor_t_ns") is not None:
                self._note_floor_lag(int(req["floor_t_ns"]))
        if self.config.durability == "strict":
            # durability=strict: this ack gates a settled round's
            # producer ack, so the records must be ON DISK before it
            # returns — strict deployments opt out of the flush_async
            # one-interval lag on the standby path too (the controller's
            # settle-side persist honors the same knob,
            # DataPlane._persist_round).
            store.flush()
            return {"ok": True}
        now = time.monotonic()
        if now - self._repl_last_flush >= 0.05:
            # Deferred fsync (SegmentStore.flush_async): the ack this
            # handler returns gates the controller's settle pipeline, so
            # it must not wait out the filesystem's fsync latency. The
            # promoted-standby boot path still runs its OWN synchronous
            # flush barrier before the replay scan (_boot_dataplane).
            flush = getattr(store, "flush_async", store.flush)
            flush()
            self._repl_last_flush = now
        return {"ok": True}

    def _note_floor_lag(self, t_push_ns: int) -> None:
        """A pushed floor has passed on the plane: observe how long
        after the controller's settle release (its time.monotonic_ns,
        this one's now - one machine's clock, as the benchmark's
        delivery stamp is; across machines the number means nothing and
        judges nothing). Every trace_sample_n-th is a follower.floor
        span of that length, ending now."""
        lag_us = max(0, (time.monotonic_ns() - t_push_ns) // 1000)
        self._m_ff_floor_lag_us.observe_int(lag_us)
        if self.spans is not None:
            tid = derive_trace_id(f"follower.floor/broker{self.broker_id}",
                                  next(self._floor_ordinal))
            if sampled(tid, self.config.trace_sample_n):
                self.spans.span_at("follower.floor", TraceContext(tid, 0),
                                   self.metrics.clock() - lag_us / 1e6,
                                   lag_us / 1e6)

    def _handle_repl_stripes(self, req: dict) -> dict:
        """Standby side of STRIPED replication (stripes/plane.py): the
        repl.rounds fences verbatim, then each frame is CRC-validated
        and persisted as a REC_STRIPE record — a frame damaged in
        flight REFUSES (`bad_stripe_frame`; the sender re-sends from
        its in-memory copy), never lands, so the store only ever holds
        frames the recovery path can trust byte-for-byte."""
        from ripplemq_tpu.storage.segment import REC_STRIPE
        from ripplemq_tpu.stripes.codec import parse_frame

        epoch = int(req["epoch"])
        view = self.manager.fence_view  # as in _handle_repl_rounds
        if epoch < view.epoch:
            return {"ok": False, "error": "stale_epoch", "epoch": view.epoch}
        if self.dataplane is not None and view.controller == self.broker_id:
            return {"ok": False, "error": "active_controller"}
        if self._store_quarantined and not self._quarantine_left_set:
            # Same stale-membership fence as repl.rounds: an emptied
            # store must not ack stripes under pre-death membership.
            return {"ok": False, "error": "store_quarantined"}
        store = self._round_store
        if store is None:
            return {"ok": False, "error": "no_store"}
        recs = []
        frames = []
        for raw in req["frames"]:
            raw = bytes(raw)
            frame = parse_frame(raw)
            if frame is None:
                return {"ok": False, "error": "bad_stripe_frame"}
            frames.append(frame)
            recs.append(
                (REC_STRIPE, frame.idx, int(frame.gsn) & 0x7FFFFFFF, raw)
            )
        # Holder-side apply spans (stripe.apply), one per sampled
        # produce whose tctx rode the batch — pairs with the sender's
        # stripe.send for the skew estimate on this edge.
        sps = ([self.spans.span("stripe.apply", ctx_from_wire(raw),
                                {"frames": len(frames)})
                for raw in req.get("tctx", ())]
               if self.spans is not None else ())
        append_many = getattr(store, "append_many", None)
        if append_many is not None:
            append_many(recs)
        else:
            for rec in recs:
                store.append(*rec)
        for s in sps:
            s.end()
        fp = self.follower_plane
        if fp is not None:
            # Feed the follower read plane's own-stripe window + gsn
            # floor (decode is lazy — reconstruct-on-read).
            for frame in frames:
                fp.ingest_stripe(epoch, frame)
        if self.config.durability == "strict":
            store.flush()
            return {"ok": True}
        now = time.monotonic()
        if now - self._repl_last_flush >= 0.05:
            flush = getattr(store, "flush_async", store.flush)
            flush()
            self._repl_last_flush = now
        return {"ok": True}

    def _handle_stripe_fetch(self, req: dict) -> dict:
        """Serve this broker's persisted stripe frames to a PROMOTED
        peer rebuilding the full stream (stripes/recovery.py): paged
        scan of REC_STRIPE records, cursor = ordinal among them. Served
        by any broker with a store, unfenced — recovery runs exactly
        when controllership is in flux."""
        from ripplemq_tpu.storage.segment import REC_STRIPE

        store = self._round_store
        if store is None:
            return {"ok": False, "error": "no_store"}

        def stripe_records():
            # The LIVE store first, then any `.prestripe-N` snapshots a
            # previous promotion of THIS broker preserved: the rebuild
            # rewrites the store to full records, and without serving
            # the preserved stripes a later promotion elsewhere could
            # find the cluster short of k (observed in the first smoke
            # as an unrecoverable-group boot loop). Yields (cursor,
            # payload) where cursor = [phase, segment, offset] — a
            # STABLE position (segments GC whole; surviving locators
            # never shift), unlike a flat ordinal, which retention trim
            # between two pages would slide under the requester,
            # silently skipping frames. A store without stable locators
            # (MemoryRoundStore) never GCs, so its record ordinal is
            # stable too.
            if hasattr(store, "scan_indexed"):
                it = store.scan_indexed()
            else:
                it = ((t, s, b, p, i) for i, (t, s, b, p)
                      in enumerate(store.scan()))
            for j, (t, _s, _b, payload, loc) in enumerate(it):
                if t != REC_STRIPE:
                    continue
                if isinstance(loc, tuple):
                    yield [0, int(loc[0]), int(loc[1])], int(_b), payload
                else:
                    yield [0, 0, j], int(_b), payload
            if self._store_dir is not None:
                import glob as _glob
                import os as _os

                from ripplemq_tpu.storage.segment import scan_store_indexed

                def _n(p):
                    try:
                        return int(p.rsplit("-", 1)[1])
                    except ValueError:
                        return 1 << 30
                dirs = sorted(
                    _glob.glob(self._store_dir + ".prestripe-*"), key=_n
                )
                for phase, d in enumerate(dirs, start=1):
                    if not _os.path.isdir(d):
                        continue
                    try:
                        for t, _s, _b, payload, loc in scan_store_indexed(d):
                            if t == REC_STRIPE:
                                yield ([phase, int(loc[0]), int(loc[1])],
                                       int(_b), payload)
                    except Exception:
                        continue  # forensic snapshot rot: best-effort

        after = req.get("after", -1)
        after = None if after in (-1, None) else list(after)
        budget = int(req.get("budget") or (32 << 20))
        # Optional gsn floor (follower reconstruct-on-read pager): skip
        # frames below it CHEAPLY off the persisted record's base field
        # — REC_STRIPE stores `gsn & 0x7FFFFFFF` there, so both sides
        # compare masked. Skipped frames still advance the served
        # cursor (`last`), keeping the pager forward-only.
        min_gsn = req.get("min_gsn")
        min_gsn = None if min_gsn is None else int(min_gsn) & 0x7FFFFFFF
        frames: list[bytes] = []
        nxt = None
        last = None
        for cursor, gsn, payload in stripe_records():
            if after is not None and cursor <= after:
                continue
            last = cursor
            if min_gsn is not None and gsn < min_gsn:
                continue
            frames.append(payload)
            budget -= len(payload)
            if budget <= 0:
                nxt = cursor
                break
        # `next` keeps its recovery-pager meaning (set only when the
        # budget clipped the scan); `last` is the cursor of the final
        # record CONSIDERED, so an incremental pager can resume past
        # everything already seen even on a short page.
        return {"ok": True, "frames": frames, "next": nxt, "last": last}

    # ---------------------------------------------------------------- duty

    def _duty_loop(self) -> None:
        while not self._stop.wait(self._duty_interval_s):
            try:
                self._batch_duty()
                self._beats_relay_duty()
                self._metadata_leader_duty()
                self._producer_pid_duty()
                self._pid_reap_duty()
                self._group_duty()
                self._abdicate_duty()
                self._fence_duty()
                self._takeover_duty()
                self._controller_duty()
                self._slot_clean_duty()
                self._standby_duty()
                self._quota_share_duty()
                self._follower_lease_duty()
                self._follower_park_duty()
                self._reconfig_duty()
                self._autosplit_duty()
                self._shard_duty()
            except Exception as e:  # duties must never kill the loop
                log.warning("broker %d duty error: %s: %s",
                            self.broker_id, type(e).__name__, e)
                with self._errors_lock:
                    self.duty_errors.append(f"{type(e).__name__}: {e}")
                    del self.duty_errors[:-20]

    def _quota_share_duty(self) -> None:
        """Cluster-level quotas: rescale this broker's per-tenant
        admission buckets by its CURRENT share of partition leaderships
        (slo/admission.py set_leadership_share) — a tenant's quota is a
        cluster rate, not rate × brokers. Floored at one partition's
        worth even with zero leaderships: admission runs before the
        leadership check in the produce handler, and a zero-rate bucket
        would answer stale-routed produces `overloaded:` instead of the
        `not_leader` redirect that re-resolves the client's routing."""
        if not self.config.slo_quotas:
            return
        total = 0
        led = 0
        for t in self.manager.get_topics():
            for a in t.assignments:
                if a.state == "retired":
                    continue
                total += 1
                if a.leader == self.broker_id:
                    led += 1
        if total <= 0:
            return
        self.slo.admission.set_leadership_share(max(led, 1) / total)

    def _follower_lease_duty(self) -> None:
        """Metadata-leader duty: keep the follower-read lease table
        equal to {standby: current epoch}. Proposed (not written) — the
        grant is replicated state, so every broker fences reads against
        the SAME table, and the OP_SET_CONTROLLER apply clearing it is
        what revokes a deposed generation everywhere at once."""
        if not self.config.follower_reads:
            return
        if self.runner.node.role != LEADER:
            return
        epoch = self.manager.current_epoch()
        desired = {int(b): epoch for b in self.manager.current_standbys()}
        if desired == self.manager.current_follower_leases():
            return
        now = time.monotonic()
        if now - self._last_lease_grant < 1.0:
            return  # debounce: a failed propose retries next tick
        self._last_lease_grant = now
        if self.propose_cmd({
            "op": OP_SET_FOLLOWER_LEASES,
            "epoch": epoch,
            "leases": {str(b): int(e) for b, e in desired.items()},
        }, retries=1):
            self.recorder.record(
                "follower_lease", epoch=epoch,
                brokers=sorted(desired),
            )

    def _reconfig_duty(self) -> None:
        """Controller: drive every open split-handoff window to
        cutover, plus every broker's local follower-plane slot prune.
        The cutover gate is the parent's SETTLED floor crossing the
        split-begin watermark — every write acked before the split
        began is then replicated to the full standby set, so the
        final routing flip survives a controller death the next
        instant. A floor that cannot advance (quorum loss mid-handoff)
        falls back to the split_handoff_timeout_s LOCAL deadline so
        the window is always bounded; the deadline clock restarts on
        failover, which delays — never loses — the cutover, because
        the handoff window itself is replicated metadata the promoted
        controller sees on its first duty pass."""
        if self.follower_plane is not None:
            # Satellite of the same transition: serve state for slots
            # the topic table no longer maps must not dangle (and a
            # reused slot must not inherit a dead partition's floor).
            self.follower_plane.prune_slots(self.manager.mapped_slots())
        dp = self._local_engine()
        if dp is None:
            self._handoff_seen.clear()
            return
        open_ho = self.manager.current_handoffs()
        for k in list(self._handoff_seen):
            if k not in open_ho:
                del self._handoff_seen[k]
        now = time.monotonic()
        for (topic, pid), ho in open_ho.items():
            first = self._handoff_seen.setdefault((topic, pid), now)
            slot = self.manager.slot_of(group_key(topic, pid))
            if slot is None:
                continue
            timed_out = (now - first
                         >= self.config.split_handoff_timeout_s)
            if dp.settled_end(slot) < int(ho["watermark"]) \
                    and not timed_out:
                continue
            csp = NULL_SPAN
            if self.spans is not None:
                tid = derive_trace_id(f"cutover/{topic}/{pid}",
                                      int(ho["watermark"]))
                if sampled(tid, self.config.trace_sample_n):
                    csp = self.spans.span(
                        "meta.cutover", TraceContext(tid, 0),
                        {"topic": topic, "partition": pid})
            ok = self.propose_cmd({
                "op": OP_SPLIT_CUTOVER, "topic": topic,
                "partition": pid, "watermark": int(ho["watermark"]),
            }, retries=1)
            csp.end(ok=ok)
            if ok and timed_out:
                log.warning(
                    "broker %d: split cutover for %s/%d forced by "
                    "handoff timeout (settled %d < watermark %d)",
                    self.broker_id, topic, pid,
                    dp.settled_end(slot), int(ho["watermark"]),
                )

    def _autosplit_duty(self) -> None:
        """Controller broker: the SLO→topology closed loop. When the
        SloController's tick history arms a split (`split_auto` with a
        sustained produce-SLO breach), propose an online split of the
        HOTTEST splittable partition — ranked by committed log-end
        growth between duty passes, a host-side observation off the
        local device plane, no device work. When the history arms a
        merge instead (deep comfortable/idle hysteresis), reabsorb one
        split child. Runs only where the device plane lives — the same
        broker whose engine-side signals feed the shed machine — so
        exactly one broker arbitrates; the apply's deterministic no-op
        guards make a raced duplicate proposal harmless regardless."""
        if not self.config.split_auto:
            return
        dp = self._local_engine()
        if dp is None:
            self._autosplit_prev_ends = {}
            return
        # Snapshot log ends EVERY pass (the ranking must already have a
        # baseline the moment the evidence arms), and rank while at it.
        prev = self._autosplit_prev_ends
        cur: dict = {}
        hottest = None
        hottest_delta = -1
        for t in self.manager.get_topics():
            for a in t.assignments:
                if a.state != "active":
                    continue
                key = group_key(t.name, a.partition_id)
                slot = self.manager.slot_of(key)
                if slot is None:
                    continue
                cur[key] = end = dp.log_end(slot)
                if a.range_hi - a.range_lo < 2:
                    continue  # too narrow to split: never a candidate
                delta = end - prev.get(key, end)
                if delta > hottest_delta:
                    hottest_delta, hottest = delta, key
        self._autosplit_prev_ends = cur
        if self.manager.current_handoffs():
            return  # one reconfiguration window in flight at a time
        if self.slo.split_wanted():
            if hottest is None or self.manager.spare_slot_count() <= 0:
                return  # stay armed; feasibility may return
            topic, pid = hottest
            if self.propose_cmd({
                "op": OP_SPLIT_PARTITION, "topic": topic,
                "partition": pid, "watermark": int(cur[hottest]),
            }, retries=1):
                self.slo.note_reconfig()
                log.warning(
                    "broker %d: auto-split %s/%d (SLO breach; log-end "
                    "delta %d this duty pass)",
                    self.broker_id, topic, pid, hottest_delta,
                )
        elif self.slo.merge_wanted():
            cands = self.manager.merge_candidates()
            if not cands:
                self.slo.note_reconfig()  # nothing to merge: disarm
                return
            topic, parent, child = cands[0]
            if self.propose_cmd({
                "op": OP_MERGE_PARTITIONS, "topic": topic,
                "parent": parent, "child": child,
            }, retries=1):
                self.slo.note_reconfig()
                log.info("broker %d: auto-merge %s/%d+%d (idle "
                         "hysteresis)", self.broker_id, topic, parent,
                         child)

    def _metadata_leader_duty(self) -> None:
        node = self.runner.node
        if node.role != LEADER:
            return
        now = time.monotonic()
        if now - self._last_membership_poll < self.config.membership_poll_s:
            return
        self._last_membership_poll = now
        with self.runner.lock:
            alive = node.alive_peers(self._alive_horizon)
        if not alive:
            return
        cmd = self.manager.plan_assignment(alive)
        if cmd is not None:
            self.runner.propose(cmd)
        # Controller failover: promote a live standby when the controller
        # is dead; prune dead standbys otherwise.
        ctrl_cmd = self.manager.plan_controller(alive)
        if ctrl_cmd is not None:
            self.runner.propose(ctrl_cmd)

    def _group_duty(self) -> None:
        """Metadata leader: evict group members whose heartbeat session
        lapsed (liveness-flap → rebalance). Eviction is an ordinary
        OP_GROUP_LEAVE — the apply bumps the generation and reassigns,
        and the member's next heartbeat/commit sees `unknown_member` /
        `fenced_generation` and rejoins. A fresh leader grants every
        member a full grace window (volatile ledger; see GroupLiveness)."""
        node = self.runner.node
        if node.role != LEADER:
            # Both ledgers are only meaningful while CONTINUOUSLY
            # leading: stamps recorded during a previous tenure are
            # stale the moment leadership is lost (members beat the new
            # leader; emptiness may have been interrupted). Clearing
            # them here is what makes re-election grant a full grace
            # window — otherwise a re-elected leader's first tick could
            # mass-evict healthy members (last beats predate the
            # interregnum) or reap a group after seconds of REAL
            # emptiness (an empty-since stamp from the previous
            # tenure).
            self._group_empty_since.clear()
            self._group_liveness.clear()
            return
        with self.manager.lock:
            table = self.manager.groups
            evict = self._group_liveness.plan_evictions(
                table, self.config.group_session_timeout_s
            )
        evict_cmds = []
        for group, member in evict:
            log.info("broker %d: evicting group member %s/%s "
                     "(session lapsed)", self.broker_id, group, member)
            self._group_liveness.forget(group, member)
            evict_cmds.append(
                {"op": OP_GROUP_LEAVE, "group": group, "member": member,
                 "reason": "evicted"}
            )
        if len(evict_cmds) == 1:
            self.propose_cmd(evict_cmds[0], retries=1)
        elif evict_cmds:
            # A session-timeout storm evicts as ONE wave: the batch
            # apply defers each group's rebalance to the wave end, so a
            # mass eviction costs one generation bump per group, not
            # one per member (the same collapse the join path gets from
            # _submit_meta).
            self.propose_cmd(
                {"op": OP_BATCH, "cmds": evict_cmds}, retries=1
            )
        # Empty-group retention: a group with zero members keeps its
        # generation and shared offsets (transient total-churn must not
        # reset the group's identity — see GroupTable.leave); only
        # after group_retention_s of CONTINUOUS emptiness on this
        # leader is it reaped, releasing the offset slot for recycling.
        # The apply re-checks emptiness, so a rejoin racing the reap
        # proposal wins.
        now = time.monotonic()
        empty = set(self.manager.empty_groups())
        for g in list(self._group_empty_since):
            if g not in empty:
                del self._group_empty_since[g]
        for g in empty:
            t0 = self._group_empty_since.setdefault(g, now)
            if now - t0 > self.config.group_retention_s:
                self._group_empty_since.pop(g, None)
                self.propose_cmd(
                    {"op": OP_GROUP_DELETE, "group": g}, retries=1
                )

    def _slot_clean_duty(self) -> None:
        """Controller: drain the recycled-consumer-slot reset queue. A
        released slot's device offset row still holds the OLD consumer's
        positions; this duty zeroes it through ordinary replicated
        offset rounds (partition by partition, only where the shadow is
        nonzero) and then proposes OP_CONSUMER_SLOT_CLEAN, returning the
        slot to the allocatable pool. Work is bounded per tick (one
        slot), and a partition that cannot commit right now (quorum
        lost) just retries next tick — the slot stays dirty, never
        allocatable, so correctness is never racing the reset."""
        dp = self._local_engine()
        if dp is None:
            return
        dirty = self.manager.dirty_slots()
        if not dirty:
            return
        cslot = dirty[0]
        futs = []
        for slot in range(dp.cfg.partitions):
            if dp.read_offset(slot, cslot) == 0:
                continue
            if dp.quorum_lost(slot):
                return  # retry the whole slot next tick
            futs.append(dp.submit_offsets(slot, [(cslot, 0)]))
        try:
            for fut in futs:
                fut.result(timeout=self.config.rpc_timeout_s)
        except Exception as e:
            log.info("broker %d: slot-clean reset for cslot %d deferred: "
                     "%s: %s", self.broker_id, cslot, type(e).__name__, e)
            return  # offsets stay dirty; retried next tick
        self.propose_cmd(
            {"op": OP_CONSUMER_SLOT_CLEAN, "slot": cslot}, retries=1
        )

    def _abdicate_duty(self) -> None:
        """Controller whose data plane broke PERMANENTLY (lockstep mesh
        break: an engine-worker process died mid-call) while the broker
        itself is alive: the metadata leader's dead-controller planning
        never fires, so the controller must surrender. Propose promotion
        of a live standby under a bumped epoch; the fence duty then
        releases the broken plane and the promoted standby's takeover
        duty boots from its copy of the committed-round stream — zero
        settled-append loss, the same guarantee as controller death
        (every settled round was acked by the full standby set)."""
        dp = self.dataplane
        if dp is None or not self._owns_dataplane:
            return
        reason = dp.broken_reason
        if reason is None:
            return
        if self.manager.current_controller() != self.broker_id:
            return  # already deposed; fence duty will release the plane
        cmd = self.manager.plan_abdication()
        if cmd is None:
            log.warning(
                "broker %d: data plane broken (%s) but no live standby "
                "to abdicate to; plane stays down", self.broker_id, reason,
            )
            return
        log.warning(
            "broker %d: data plane broken (%s); abdicating controllership "
            "to broker %d (epoch %d)",
            self.broker_id, reason, cmd["controller"], cmd["epoch"],
        )
        self.recorder.record("abdicate", reason=str(reason)[:200],
                             successor=cmd["controller"],
                             epoch=cmd["epoch"])
        self.propose_cmd(cmd)
        # The apply flips current_controller; the fence duty (same duty
        # pass) releases the broken plane.

    def _fence_duty(self) -> None:
        """Deposed controller: release the device program and revert to a
        plain frontend (its round store keeps its copy of the stream; the
        new controller re-admits it to the standby set via catch-up)."""
        if self.dataplane is None or not self._owns_dataplane:
            return
        if self.manager.current_controller() == self.broker_id:
            return
        log.info(
            "broker %d: deposed as controller (epoch %d now at broker %s); "
            "releasing the device program",
            self.broker_id, self.manager.current_epoch(),
            self.manager.current_controller(),
        )
        self.recorder.record(
            "deposed", epoch=self.manager.current_epoch(),
            successor=self.manager.current_controller(),
        )
        dp = self.dataplane
        self.dataplane = None
        self.manager.detach_dataplane()
        if self._replicator is not None:
            self._replicator.stop()
            self._replicator = None
        dp.stop()  # fails queued/in-flight rounds → producers re-route
        self._owns_dataplane = False

    def _metadata_current(self) -> bool:
        """Freshness gate for acting on metadata that names THIS broker
        controller: True once the locally applied metadata provably
        includes every entry the cluster committed before this process
        (re)booted. As metadata leader, winning the election proves the
        log is complete (Raft §5.4.1) and the election no-op barrier
        drives commit to the log end — require it applied. As follower,
        require application up to the highest commit the current leader
        advertised (`max_commit_seen`, volatile per process lifetime —
        recovered state never satisfies it by itself). Until contact
        with the current metadata quorum, recovered controllership is
        treated as a CLAIM, not a fact."""
        node = self.runner.node
        with self.runner.lock:
            if node.role == LEADER:
                return node.last_applied >= node.last_index()
            return (node.leader_hint is not None
                    and node.max_commit_seen > 0
                    and node.last_applied >= node.max_commit_seen)

    def _apply_committed(self, index: int, cmd: dict) -> None:
        """Metadata apply hook (RaftNode.apply_fn): delegates to the
        manager, then records whether this process has WITNESSED a live
        transition into its own controllership. Judged by state change
        rather than op shape so OP_BATCH wrapping and future op forms
        stay covered; gated on the apply index so entries replayed out
        of the restored log (index <= _recovered_raft_end) never count
        as a live promotion."""
        prev = self.manager.current_controller()
        self.manager.apply(index, cmd)
        if (not self._promoted_live
                and index > self._recovered_raft_end
                and prev != self.broker_id
                and self.manager.current_controller() == self.broker_id):
            self._promoted_live = True

    def _takeover_duty(self) -> None:
        """Promoted standby (and genesis/restarted controller): boot the
        device program from the local copy of the committed-round
        stream. Every settled round was acked by every standby-set
        member before its producer saw success, so no committed entry
        is lost across the handover. Gated on metadata freshness: a
        restarted broker's recovered metadata may name it controller in
        an epoch the cluster has already left (see __init__)."""
        if self._store_quarantined:
            in_set = self.broker_id in self.manager.current_standbys()
            if not in_set:
                self._quarantine_left_set = True
            elif self._quarantine_left_set:
                # Out-then-in: the controller pruned this broker after
                # the quarantine (repl acks refused until then) and
                # re-admitted it through the full catch-up stream — set
                # membership is proposed only after the whole store
                # prefix (plus buffered live rounds) transferred, so the
                # reopened store is whole again. Cleared HERE — while
                # still a standby — because the promotion that might
                # follow removes the promoted broker from the standby
                # list in the same apply. Membership WITHOUT the
                # out-transition is stale pre-death metadata and proves
                # nothing (a promoted stale member served an emptied
                # history as truth in the proc disk-fault drills).
                self._store_quarantined = False
                self._quarantine_left_set = False
        if self.dataplane is not None:
            return
        if self.manager.current_controller() != self.broker_id:
            # Not (or no longer) the controller: any FUTURE promotion
            # starts with the full boot-failure grace — without this
            # reset, a broker that once abdicated over boot failures
            # would re-abdicate on its first hiccup when re-promoted.
            self._boot_failures = 0
            return
        if self._round_store is None:
            return
        if not self._metadata_current():
            return  # recovered claim unconfirmed; retry next duty tick
        if self._store_quarantined:
            # The local stream was quarantined at boot (disk damage
            # beyond repair) and the store reopened EMPTY: booting a
            # plane from it would serve an empty history as truth —
            # acked loss by construction. Hand controllership to a
            # standby holding the real stream; this broker rejoins as a
            # standby and the flag clears once catch-up re-admits it
            # (the check at the top of this duty).
            cmd = self.manager.plan_abdication()
            if cmd is not None:
                log.warning(
                    "broker %d: refusing to boot a plane from a "
                    "quarantined store; abdicating to broker %d",
                    self.broker_id, cmd["controller"],
                )
                self.propose_cmd(cmd)
                return
            # No live standby to hand to: the quarantined copy was the
            # best anyone has — boot empty rather than stall the whole
            # cluster forever (genesis-equivalent restart).
            log.warning(
                "broker %d: quarantined store and no standby to "
                "abdicate to; booting empty", self.broker_id,
            )
            self._store_quarantined = False
        if not self._promoted_live and self._recovered_raft_end > 0:
            # This controllership claim was RECOVERED from disk, not
            # won while running (genesis boots restore nothing; every
            # live promotion flips _promoted_live in _apply_committed).
            # A restarted controller's stream may have silently lost
            # its acked tail — a torn tail is repaired by DROPPING it,
            # a legitimate crash artifact — so booting from it can
            # serve a shorter history than what producers were acked
            # against (the proc split-chaos drill caught this as an
            # offset regression: a commit acked 12 ms before SIGKILL
            # vanished across the restart). Every settled round was
            # acked by every standby-set member first, so hand
            # controllership to one and let the whole copy win; this
            # broker rejoins through catch-up like any abdication.
            cmd = self.manager.plan_abdication()
            if cmd is not None:
                log.warning(
                    "broker %d: restarted into a recovered controller "
                    "claim; abdicating to broker %d rather than boot "
                    "from a possibly torn local stream",
                    self.broker_id, cmd["controller"],
                )
                self.propose_cmd(cmd)
                return
            # No live standby to hand to (or the recovered standby set
            # is empty): the local copy is the best anyone has — adopt
            # the claim and boot, same fallback as quarantine.
            self._promoted_live = True
        self._boot_dataplane()

    def _controller_duty(self) -> None:
        dp = self._local_engine()
        if dp is None:
            return
        # Touch the device ONLY when there is work: the log-ends fetch
        # holds the device lock for a full host-device round trip, and
        # a duty loop fetching every tick starves the dispatch pipeline.
        # Elections have a cheap host-side pre-check; repairs run on
        # their own cadence.
        # Repair scans defer while the plane is busy (the fetch would
        # drain the dispatch pipeline; see DataPlane.busy) — but never
        # beyond 30 s, so lagging replicas still catch up under
        # sustained load. Busy is judged with hysteresis: under
        # intermittent traffic (e.g. a consume drain whose offset
        # commits ride spaced quorum rounds) a POINT sample of busy()
        # flickers False between rounds, and a repair scan fired into
        # that gap stalls the next ~1 s of dispatches behind its fetch
        # (measured: the r4 consume drain spent more time in duty-loop
        # log_ends fetches than in its own commit rounds). The plane
        # must have looked idle for 10 consecutive duty ticks before an
        # optional scan touches the device.
        now = time.monotonic()
        if dp.busy():
            self._engine_busy_at = now
        since_repair = now - self._last_repair_scan
        idle_for = now - self._engine_busy_at
        due_repairs = since_repair >= max(1.0, self._duty_interval_s * 10)
        if (due_repairs and since_repair < 30.0
                and idle_for < max(0.5, self._duty_interval_s * 10)):
            due_repairs = False
        if not self.manager.needs_elections() and not due_repairs:
            return
        # One [R, P] log-ends snapshot per pass, shared by both planners
        # (elections don't move log ends, so the snapshot stays valid).
        log_ends = dp.log_ends()
        cands, drafts = self.manager.plan_elections(log_ends)
        if drafts:
            winners = dp.elect(cands) if cands else {}
            won = [drafts[slot] for slot, w in winners.items() if w]
            # Vote-less drafts (device-term-skew heals): the device
            # already granted the term; only the advert is missing.
            won += [d for slot, d in drafts.items() if slot not in cands]
            # ONE replicated command advertises every winner of the
            # batched ballot (chunked to bound the entry size): a
            # thousand-partition election wave — bootstrap or failover —
            # must not pay a thousand per-proposal broadcast costs.
            for i in range(0, len(won), 512):
                chunk = won[i : i + 512]
                if len(chunk) == 1:
                    self.propose_cmd(chunk[0], retries=1)
                else:
                    self.propose_cmd({"op": OP_BATCH, "cmds": chunk},
                                     retries=1)
        # Periodic lag repair: catch up alive followers that trail their
        # leader (covers post-election catch-up and slots that came alive
        # while the partition was leaderless).
        if due_repairs:
            self._last_repair_scan = time.monotonic()
            for (src, dst), slots in self.manager.plan_repairs(log_ends).items():
                dp.resync(src, dst, slots)

    def _standby_duty(self) -> None:
        """Controller: maintain the standby set — drop suspects stalling
        the settle path, admit new members after catch-up (the join
        protocol of broker/replication.py)."""
        rep = self._replicator
        if rep is None or self._local_engine() is None:
            return
        rep.sync_members()
        suspects = rep.take_suspects()
        if suspects:
            members = [
                s for s in self.manager.current_standbys()
                if s not in suspects
            ]
            self.propose_cmd(
                {"op": OP_SET_STANDBYS,
                 "epoch": self.manager.current_epoch(),
                 "standbys": members},
                retries=1,
            )
        if self._catchup_thread is not None:
            if self._catchup_thread.is_alive():
                return
            self._catchup_thread = None
        if self._round_store is None:
            return
        cand = self.manager.plan_standby_add(self.config.standby_count)
        if cand is None or rep.is_joining(cand):
            return
        t = threading.Thread(
            target=self._run_catchup, args=(cand,), daemon=True,
            name=f"catchup-{self.broker_id}-to-{cand}",
        )
        self._catchup_thread = t
        t.start()

    def _run_catchup(self, cand: int) -> None:
        """Stream the full store prefix to `cand`, then propose its
        standby-set membership (live rounds buffer behind the scan and
        flow to the joiner meanwhile, so the stream is gap-free)."""
        rep = self._replicator
        epoch = self.manager.current_epoch()
        joined = False
        try:
            rep.catchup(cand, self._round_store)
            members = sorted(set(self.manager.current_standbys()) | {cand})
            # The joiner holds the full prefix AND keeps receiving live
            # rounds (it stays in the joining set), so a lagging
            # membership commit is retried by RE-PROPOSING — never by
            # re-streaming the store (under produce load the metadata
            # apply can trail by seconds, and a from-scratch catch-up
            # retry loop would amplify exactly the load that caused the
            # lag).
            for _ in range(5):
                if not self.propose_cmd(
                    {"op": OP_SET_STANDBYS, "epoch": epoch,
                     "standbys": members},
                    retries=3,
                ):
                    continue
                deadline = time.monotonic() + max(
                    10.0, self.config.rpc_timeout_s
                )
                while time.monotonic() < deadline:
                    if cand in self.manager.current_standbys():
                        joined = True
                        break
                    if self.manager.current_epoch() != epoch:
                        return  # deposed mid-join; fence duty cleans up
                    time.sleep(0.02)
                if joined:
                    break
            if joined:
                self.recorder.record("standby_joined", standby=cand,
                                     epoch=epoch)
                log.info("broker %d: standby %d caught up and joined the "
                         "standby set", self.broker_id, cand)
            else:
                log.warning("broker %d: catchup(%d) membership proposal "
                            "failed; will retry", self.broker_id, cand)
                with self._errors_lock:
                    self.duty_errors.append(
                        f"catchup({cand}): membership proposal failed; "
                        f"will retry")
                    del self.duty_errors[:-20]
        except Exception as e:
            log.warning("broker %d: catchup(%d) failed: %s: %s",
                        self.broker_id, cand, type(e).__name__, e)
            with self._errors_lock:
                self.duty_errors.append(
                    f"catchup({cand}): {type(e).__name__}: {e}"
                )
                del self.duty_errors[:-20]
        finally:
            # Success AND failure both leave the joining state: a joined
            # member now acks via the set; a failed join is fully unwound
            # (sync_members prunes the sender) so the next duty pass
            # retries the catch-up from scratch — replay is later-record-
            # wins, so re-streamed duplicates are harmless.
            rep.finish_join(cand)
