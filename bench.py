"""Benchmark: committed-appends/sec + produce-ack latency percentiles.

Prints ONE JSON line:
  {"metric": "committed_appends_per_sec", "value": N, "unit": "appends/s",
   "vs_baseline": N, "baseline_appends_per_sec": N,
   "shipped_shape_appends_per_sec": N,
   "p50_ack_ms": N, "p99_ack_ms": N, "p999_ack_ms": N,
   "round_rtt_ms": N, "operating_curve": [...],
   "consume_msgs_per_sec": N, "spmd_parity": {...},
   "e2e_appends_per_sec": N, "e2e_mb_per_sec": N,
   "readback": "verified", "e2e_readback": "verified"}

Field map:
- `value` — the ENGINE number: STEADY-STATE quorum rounds on device,
  input resident, ring wrapping behind the host-advanced trim watermark
  exactly as the broker drives retention (`_run_sustained`).
- `burst_window_appends_per_sec` — the r3/r4 headline method (fresh
  ring, one slots/B-round window), kept for cross-round comparability;
  its window pays ~85 ms of fixed cost it cannot amortize (PROFILE.md).
- `e2e_appends_per_sec` — the SYSTEM number: fresh distinct payloads
  through producer clients → TCP → broker dispatch → batcher → device
  rounds → store + standby replication (`_run_e2e`); nothing replayed.
- `e2e_consume_msgs_per_sec` — the SYSTEM consume number: consumer
  clients over TCP draining the topic the e2e phase just produced
  (socket → dispatch → host-mirror read → codec → auto-commit),
  count-verified against the produce acks.
- `shipped_shape_appends_per_sec` — the engine measured at the
  examples/cluster.yaml shape users actually boot.
- `operating_curve` — (coalesce_s, chain_depth) → appends/s + p50/p99,
  so the latency figures are points on a published curve.
- `consume_msgs_per_sec` — host-ring-mirror consume drain (zero device
  dispatch on the hot path; see broker/dataplane.py).
- `spmd_parity` — local (vmap) vs spmd (shard_map, 1x1 mesh) dispatch
  on the same chip; delta_pct must stay small for the production
  binding to be trusted at the local binding's numbers. The spmd arm
  runs the FUSED control binding (the production default) with the
  legacy-control shard_map binding recorded as the A/B arm.
- `spmd_scaling` — sustained fused-spmd committed appends/s with
  partitions sharded over the "part" mesh axis at 1/2/4/8 devices
  (virtual CPU mesh, one subprocess per count; the virtual devices
  share one host's FLOPs, so the curve prices sharding overhead, not
  added silicon — profiles/spmd_scaling.py is the standalone harness).
- `control_fusion_ab` — same-process A/B of the fused-control and
  packed-write levers (EngineConfig.fused_control / .packed_writes)
  vs the legacy path: control-only ms/round, full and quarter-batch
  sustained rates (also standalone: profiles/control_ab.py).
- `host_plane_scaling` — the multi-core host plane's same-host worker
  sweep (ISSUE 12): full e2e topology at `host_workers` 1/2/4 per
  broker, subprocess clients, identical best-of-N method and
  count-exact readback per arm; `scaling_x` = best arm / workers-1
  baseline, `host_cores` the parallelism physically present.

`round_rtt_ms` is the measured single-round dispatch+fetch time on this
chip — the floor any ack latency pays; read the percentiles against
it. `baseline_appends_per_sec` is the absolute denominator of
`vs_baseline`, recorded so the ratio is auditable from this artifact
alone; numerator and denominator are measured with the SAME sustained
method (a methodology switch on one side would silently change the
ratio's meaning across rounds).

What is measured (BASELINE.md metric: committed-appends/sec/chip on a
5-replica partition, 1k-partition fan-out config; p99 ack alongside):

- **TPU mode**: the production configuration — 1024 partitions × RF 5,
  full 256-entry batches per partition per round, psum quorum commit —
  dispatched as CHAINS of 8 complete quorum rounds per launch (the
  engine's step_many scan path, which the broker's burst drain uses for
  deep backlogs; dispatch latency is the fixed cost that dominates small
  rounds, so chaining it away measures the engine, not the launch
  overhead). Every entry counted was quorum-committed, and a sample of
  appended payloads is READ BACK and byte-compared after the timed
  rounds (a kernel DMA-ing garbage would fail the bench, not just the
  docs).

- **Baseline mode** (the denominator of vs_baseline): the reference's
  architecture executed on the SAME hardware — ONE message per
  replication round on ONE 5-replica partition, rounds strictly
  sequential. That is the reference's hot loop shape: one Raft task per
  message per `node.apply` (reference:
  mq-broker/.../MessageAppendRequestProcessor.java:59, one message per
  client RPC — mq-common/.../PartitionClient.java:39 — with no client
  pipelining, SURVEY.md §3.2). The reference publishes no numbers and a
  JVM cluster is not runnable here (BASELINE.md), so the architectural
  pattern measured on identical silicon is the fairest available
  denominator — generous to the reference, since it pays neither JRaft's
  fsync nor Java serialization.

- **p99_ack_ms**: produce-ack latency measured through the FULL host
  batcher (DataPlane.submit_append → future resolve), 16 concurrent
  submitters of single-message appends over 1024 partitions — the stack
  where latency actually accrues. Reference behavior being beaten: one
  sync 3 s-timeout RPC per message (PartitionClient.java:45).

Timing honesty: every timed region ends with a host fetch of a value
data-dependent on the last round (`np.asarray(out.committed)`), so the
window closes only when the device has finished the work it counts.
"""

from __future__ import annotations

import json
import time

import numpy as np

PAYLOAD = b"bench-payload-" + b"x" * 86  # 100 bytes, recognizable prefix


def _make(cfg):
    from ripplemq_tpu.core.encode import build_step_input
    from ripplemq_tpu.parallel.engine import make_local_fns

    fns = make_local_fns(cfg)
    alive = np.ones((cfg.partitions, cfg.replicas), bool)
    quorum = np.full((cfg.partitions,), cfg.quorum, np.int32)
    return fns, alive, quorum, build_step_input


def _read_and_check(fns, state, replica: int, p: int, offset: int,
                    batch: int, where: str) -> None:
    """Walk the read window from `offset` until `batch` messages arrived
    and byte-compare each against PAYLOAD (shared by the burst-window
    and sustained verifiers — one read-walk implementation to fix)."""
    from ripplemq_tpu.core.encode import decode_entries

    msgs: list[bytes] = []
    while len(msgs) < batch:  # reads window read_batch rows
        data, lens, count = fns.read(
            state, np.int32(replica), np.int32(p), np.int32(offset)
        )
        got = decode_entries(data, lens, count)
        assert got, f"readback {where}: {len(msgs)} of {batch} messages"
        msgs.extend(got)
        offset += int(count)
    for m in msgs[:batch]:
        assert m == PAYLOAD, (
            f"readback {where}: corrupt payload {m[:24]!r}..."
        )


def _verify_readback(cfg, fns, state, rounds: int, batch: int) -> None:
    """Byte-compare a sample of appended payloads across partitions,
    rounds, and replicas (rounds advance the log by ALIGN-padded windows
    from a fresh init, so round r of partition p starts at row r*adv)."""
    from ripplemq_tpu.core.config import ALIGN

    adv = -(-batch // ALIGN) * ALIGN
    parts = sorted({0, 1, cfg.partitions // 2, cfg.partitions - 1})
    some_rounds = sorted({0, rounds // 2, rounds - 1})
    for p in parts:
        for r in some_rounds:
            for replica in (0, cfg.replicas - 1):
                _read_and_check(
                    fns, state, replica, p, r * adv, batch,
                    f"partition {p} round {r} replica {replica}",
                )


def _run_mode(cfg, batch_per_partition: int, rounds: int, warmup: int,
              verify: bool = False, chain: int = 1) -> float:
    """Burst-window committed-appends/sec (the r3/r4 headline method):
    a fresh ring, one timed window of `rounds` rounds — kept as the
    cross-round comparability row. The window pays a large fixed cost
    (state init + first-launch + final fetch, ~85 ms measured r5, see
    PROFILE.md) amortized over at most slots/B rounds, which is why
    `_run_sustained` replaced it as the headline. `chain` > 1 dispatches
    rounds in chains of that depth via the engine's step_many scan path
    (each chain element is a complete quorum round)."""
    import jax

    fns, alive, quorum, build = _make(cfg)
    appends = {
        p: [PAYLOAD] * batch_per_partition for p in range(cfg.partitions)
    }
    one = build(cfg, appends=appends, leader=0, term=1)
    if chain > 1:
        assert rounds % chain == 0
        inp = jax.device_put(jax.tree.map(
            lambda x: np.broadcast_to(x, (chain,) + x.shape).copy(), one
        ))
        launch = lambda st: fns.step_many(st, inp, alive, quorum)
        launches = rounds // chain
    else:
        inp = jax.device_put(one)
        launch = lambda st: fns.step(st, inp, alive, quorum)
        launches = rounds

    state = fns.init()
    for _ in range(warmup):
        state, out = launch(state)
    assert bool(np.asarray(out.committed).all()), "warmup round failed"

    state = fns.init()  # fresh log so timed rounds never hit capacity
    t0 = time.perf_counter()
    for _ in range(launches):
        state, out = launch(state)
    committed = np.asarray(out.committed)  # host fetch = execution fence
    dt = time.perf_counter() - t0
    assert bool(committed.all()), "timed round failed"
    total = rounds * cfg.partitions * batch_per_partition
    if verify:
        _verify_readback(cfg, fns, state, rounds, batch_per_partition)
    return total / dt


def _run_sustained(cfg, chain: int = 8, launches: int = 480,
                   windows: int = 3, verify: bool = True,
                   batch_per_partition: int | None = None,
                   partitions: int | None = None) -> float:
    """STEADY-STATE committed-appends/sec: the ring WRAPS. The host
    advances the trim watermark ahead of each launch exactly as the
    broker does once rows are persisted (DataPlane drain raises trim to
    the persisted prefix; core/step.py gates capacity on
    `base + B - trim <= S`), so the timed window is bounded by the
    engine's round cost — not by the ring size, which caps the r3/r4
    burst-window method at slots/B rounds and lets a ~85 ms fixed
    window cost (init + first-launch + final D2H fetch) dominate the
    figure (PROFILE.md r5 section). Launches pipeline asynchronously
    (dispatch is async; the state dependency chains execution on
    device), and the final `np.asarray(out.committed)` fences the whole
    window. Every round is a complete quorum round; committed is
    asserted for every chained round of the final launch and the timed
    state's ring tail is byte-verified after the clock stops."""
    import jax

    from ripplemq_tpu.core.config import ALIGN

    fns, alive, quorum, build = _make(cfg)
    bpp = cfg.max_batch if batch_per_partition is None else batch_per_partition
    nparts = cfg.partitions if partitions is None else partitions
    adv_round = -(-bpp // ALIGN) * ALIGN  # ALIGN-padded rows per round
    one = build(cfg, appends={p: [PAYLOAD] * bpp for p in range(nparts)},
                leader=0, term=1)
    inp = jax.device_put(jax.tree.map(
        lambda x: np.broadcast_to(x, (chain,) + x.shape).copy(), one
    ))
    adv = chain * adv_round  # rows per launch per appending partition
    trims = _stage_trims(cfg, adv, launches, jax.device_put,
                         adv_round=adv_round)
    _sustained_warmup(fns, inp, alive, quorum, trims)
    best = 0.0
    for _ in range(windows):
        rate, state = _sustained_window(
            fns, inp, alive, quorum, trims, launches * chain * bpp * nparts
        )
        if rate > best:
            best = rate
            if verify:
                # Verify THIS window's tail now, between windows: pinning
                # the state for a post-loop check would hold a second
                # full engine state (8.3 GB at the headline shape) across
                # the next window's init — over the HBM budget.
                _verify_ring_tail(fns, state,
                                  total_rows=launches * adv,
                                  batch=bpp, adv_round=adv_round,
                                  nparts=nparts)
        del state
    return best


def _stage_trims(cfg, adv: int, launches: int, put,
                 adv_round: int | None = None) -> list:
    """Stage every launch's trim watermark on device BEFORE the timed
    window — trim k lets launch k's rounds wrap the ring exactly as the
    broker's persisted-prefix trim does. A per-launch host numpy
    argument instead costs a blocking H2D transfer that serializes the
    pipeline (measured 2.4x on the single-partition baseline shape).

    The capacity rule reserves the FULL max_batch window
    (`base + B - trim <= S`, core/step.py) even when a round advances
    fewer rows, so partial-batch windows (adv_round < B) need the trim
    pushed `B - adv_round` rows further ahead than their own growth."""
    reserve = cfg.max_batch - (cfg.max_batch if adv_round is None
                               else adv_round)
    return [
        put(np.full((cfg.partitions,),
                    max(0, (k + 1) * adv + reserve - cfg.slots), np.int32))
        for k in range(launches)
    ]


def _sustained_warmup(fns, inp, alive, quorum, trims) -> None:
    state, out = fns.step_many(fns.init(), inp, alive, quorum, trims[0])
    assert bool(np.asarray(out.committed).all()), "warmup launch failed"


def _sustained_window(fns, inp, alive, quorum, trims, work: float):
    """ONE timed steady-state window from a fresh state (the sustained
    method's core, shared by the headline and the SPMD parity A/B so the
    two cannot measure different methods): dispatches pipeline
    asynchronously, the final committed fetch fences, every chained
    round of the final launch is asserted committed. Returns
    (rate, final state); the caller may verify the state's ring tail
    but must DROP it before the next window."""
    state = fns.init()
    t0 = time.perf_counter()
    for trim in trims:
        state, out = fns.step_many(state, inp, alive, quorum, trim)
    committed = np.asarray(out.committed)  # host fetch = execution fence
    dt = time.perf_counter() - t0
    assert bool(committed.all()), "sustained round failed"
    return work / dt, state


def _verify_ring_tail(fns, state, total_rows: int, batch: int,
                      adv_round: int, nparts: int,
                      tail_rounds: int = 3) -> None:
    """Byte-compare payloads from the last ring-resident rounds of the
    sustained run (earlier rounds were legitimately overwritten after
    trim passed them — that is the retention contract, not data loss)."""
    # Guard small shapes: partition 1 does not exist at nparts=1 (the
    # engine's read clips out-of-range ids to 0, which would silently
    # re-verify partition 0 and overstate coverage).
    parts = sorted({0, nparts // 2, nparts - 1}
                   | ({1} if nparts > 1 else set()))
    for p in parts:
        for r in range(tail_rounds):
            offset = total_rows - (r + 1) * adv_round
            _read_and_check(
                fns, state, 0, p, offset, batch,
                f"sustained partition {p} offset {offset}",
            )


def _run_control_only(cfg, chain: int = 8, launches: int = 240,
                      windows: int = 3) -> float:
    """CONTROL-PHASE rounds/s, sustained method: offsets-only rounds
    commit (has_work) but advance no log rows, so the wrote_rows gate
    skips the append kernel entirely — what remains per round is the
    ballot + bookkeeping + offset blend, i.e. the control phase the
    PROFILE.md r5 decomposition priced at ~0.445 ms at the headline
    shape. This is the empty-round side of the fusion A/B: run it with
    cfg.fused_control on/off (same process) and compare ms/round."""
    import jax

    fns, alive, quorum, build = _make(cfg)
    one = build(
        cfg,
        offset_updates={p: [(0, 1)] for p in range(cfg.partitions)},
        leader=0, term=1,
    )
    inp = jax.device_put(jax.tree.map(
        lambda x: np.broadcast_to(x, (chain,) + x.shape).copy(), one
    ))
    # No log growth -> trim stays zero; stage it once per launch so the
    # timed loop matches the sustained path's call shape exactly.
    zero_trim = jax.device_put(np.zeros((cfg.partitions,), np.int32))
    trims = [zero_trim] * launches
    _sustained_warmup(fns, inp, alive, quorum, trims)
    best = 0.0
    for _ in range(windows):
        rate, state = _sustained_window(
            fns, inp, alive, quorum, trims, launches * chain
        )
        best = max(best, rate)  # rounds/s
        del state
    return best


def _run_fusion_ab(chain: int = 8, launches: int = 240,
                   control_launches: int = 240, windows: int = 2,
                   shape: dict | None = None) -> dict:
    """Same-process A/B of the two r5 levers (ISSUE 1 tentpole):
    fused control and packed writes vs the legacy path, at the headline
    shape unless overridden. Control-only rounds isolate the control
    phase (target: 0.445 ms -> <=0.35 ms/round on the measuring host);
    full rounds measure the end effect on committed appends/s. Each
    variant runs its complete best-of-N windows in sequence within one
    process (best-of-N absorbs additive noise the way the spmd-parity
    A/B's alternation does, but slow drift BETWEEN variants — thermal,
    background load — lands in the deltas: treat small cross-variant
    differences as bounded by the run-to-run variance, not resolved).
    `python profiles/control_ab.py` runs this standalone."""
    from ripplemq_tpu.core.config import ALIGN, EngineConfig

    base = dict(
        partitions=1024, replicas=5, slots=12352, slot_bytes=128,
        max_batch=256, read_batch=32, max_consumers=64,
        max_offset_updates=8,
    )
    base.update(shape or {})
    variants = {
        "legacy": {},
        "fused": dict(fused_control=True),
        "packed": dict(packed_writes=True),
        "fused_packed": dict(fused_control=True, packed_writes=True),
    }
    out = {"config": (f"P={base['partitions']} R={base['replicas']} "
                      f"B={base['max_batch']} chain={chain} sustained")}
    for name in ("legacy", "fused"):
        cfg = EngineConfig(**base, **variants[name])
        rate = _run_control_only(cfg, chain=chain,
                                 launches=control_launches,
                                 windows=windows)
        out[f"control_ms_per_round_{name}"] = round(1e3 / rate, 4)
    for name, kw in variants.items():
        cfg = EngineConfig(**base, **kw)
        rate = _run_sustained(cfg, chain=chain, launches=launches,
                              windows=windows, verify=True)
        out[f"sustained_appends_per_sec_{name}"] = round(rate, 1)
    # Partial rounds are where packed writes move fewer bytes (a full
    # B-row round's extent IS the full window): quarter-full batches,
    # the bursty-broker shape, legacy vs both-levers.
    partial = max(ALIGN, base["max_batch"] // 4)
    for name in ("legacy", "fused_packed"):
        cfg = EngineConfig(**base, **variants[name])
        rate = _run_sustained(cfg, chain=chain, launches=launches,
                              windows=windows, verify=True,
                              batch_per_partition=partial)
        out[f"partial_b{partial}_appends_per_sec_{name}"] = round(rate, 1)
    out["control_speedup"] = round(
        out["control_ms_per_round_legacy"]
        / out["control_ms_per_round_fused"], 3)
    out["sustained_speedup_fused_packed"] = round(
        out["sustained_appends_per_sec_fused_packed"]
        / out["sustained_appends_per_sec_legacy"], 3)
    out[f"partial_b{partial}_speedup_fused_packed"] = round(
        out[f"partial_b{partial}_appends_per_sec_fused_packed"]
        / out[f"partial_b{partial}_appends_per_sec_legacy"], 3)
    return out


def _run_latency(cfg, submitters: int = 16,
                 per_thread: int = 250) -> dict[str, float]:
    """Submit→ack latency percentiles (ms) through the DataPlane batcher
    under concurrent single-message producers."""
    import threading

    from ripplemq_tpu.broker.dataplane import DataPlane

    dp = DataPlane(cfg, mode="local")
    dp.start()
    try:
        for p in range(cfg.partitions):
            dp.set_leader(p, 0, 1)
        # Warm every program the measured run can hit (single + chained
        # rounds at active-set buckets 8 and 32) via the same
        # DataPlane.warm() brokers run at boot — queue-coalescing races
        # could otherwise skip a shape and charge its multi-second XLA
        # compile to the measured p999.
        dp.warm(buckets=(8, 32))
        dp.submit_append(0, [PAYLOAD]).result(timeout=120)  # host path warm
        lats: list[float] = []
        errors: list = []

        def worker(tid: int) -> None:
            try:
                rng = np.random.default_rng(tid)
                slots = rng.integers(0, cfg.partitions, size=per_thread)
                for slot in slots:
                    t0 = time.perf_counter()
                    dp.submit_append(int(slot), [PAYLOAD]).result(timeout=60)
                    lats.append(time.perf_counter() - t0)
            except Exception as e:  # a dead thread must fail the run,
                errors.append((tid, repr(e)))  # not skew the percentiles

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(submitters)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, f"latency submitters failed: {errors}"
        assert len(lats) == submitters * per_thread
        a = np.asarray(lats) * 1e3
        return {
            "p50": float(np.percentile(a, 50)),
            "p99": float(np.percentile(a, 99)),
            "p999": float(np.percentile(a, 99.9)),
        }
    finally:
        dp.stop()


def _run_consume(cfg, consumers: int = 16, rows_per_part: int = 96,
                 read_q: int = 32) -> float:
    """Sustained consume throughput (messages/sec): `consumers` threads
    drain every partition through DataPlane.read — the read-coalescer
    batches their concurrent polls into read_many dispatches (each
    dispatch costs one host<->device round trip, so msgs/s ~= Q x
    read_batch / round trip)."""
    import threading

    from ripplemq_tpu.broker.dataplane import DataPlane

    dp = DataPlane(cfg, mode="local", read_q=read_q)
    dp.start()
    try:
        for p in range(cfg.partitions):
            dp.set_leader(p, 0, 1)
        batches = rows_per_part // cfg.max_batch
        futs = [
            dp.submit_append(p, [PAYLOAD] * cfg.max_batch)
            for p in range(cfg.partitions)
            for _ in range(batches)
        ]
        for f in futs:
            f.result(timeout=600)
        total = cfg.partitions * batches * cfg.max_batch
        drained = [0] * consumers
        per = cfg.partitions // consumers

        def worker(tid: int) -> None:
            for p in range(tid * per, (tid + 1) * per):
                offset = 0
                while True:
                    msgs, nxt = dp.read(p, offset, replica=0)
                    drained[tid] += len(msgs)
                    if nxt - offset < cfg.read_batch:
                        break  # caught up to commit: no empty tail poll
                    offset = nxt

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(consumers)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert sum(drained) == total, (sum(drained), total)
        return total / dt
    finally:
        dp.stop()


def _run_curve(cfg, points=None, submitters: int = 16,
               per_thread: int = 120) -> list[dict]:
    """Latency/throughput operating curve: the same concurrent-producer
    workload measured at several (coalesce_s, chain_depth) operating
    points, so the published p50/p99 is a point on a curve, not one
    configuration's anecdote. Offered load is fixed (submitters x
    single-message appends, resubmitted on ack), so each point trades
    ack latency against batching efficiency."""
    import threading

    from ripplemq_tpu.broker.dataplane import DataPlane

    points = points or [
        {"coalesce_s": 0.0, "chain_depth": 1},
        {"coalesce_s": 0.002, "chain_depth": 4},   # shipped defaults
        {"coalesce_s": 0.005, "chain_depth": 8},
        {"coalesce_s": 0.02, "chain_depth": 8},
        # Offered-LOAD points (r4 verdict weak-#4: 16 synchronous
        # single-message submitters never build a backlog deep enough to
        # engage chain_depth, so the curve's rounds_per_dispatch was
        # pinned at 1.0 and the (coalesce, chain) surface was unmapped).
        # `window` keeps that many submits in flight per producer and
        # `parts` concentrates them, so per-slot backlogs exceed
        # max_batch and the drain actually CHAINS rounds — chain_depth's
        # latency cost measured at an operating point that uses it.
        {"coalesce_s": 0.002, "chain_depth": 4, "window": 32, "parts": 4},
        {"coalesce_s": 0.005, "chain_depth": 8, "window": 64, "parts": 4},
    ]
    curve = []
    for pt in points:
        window = pt.get("window", 1)
        parts = pt.get("parts", cfg.partitions)
        dp = DataPlane(cfg, mode="local", coalesce_s=pt["coalesce_s"],
                       chain_depth=pt["chain_depth"])
        dp.start()
        try:
            for p in range(cfg.partitions):
                dp.set_leader(p, 0, 1)
            dp.warm(buckets=(8, 32))
            dp.submit_append(0, [PAYLOAD]).result(timeout=120)
            lats: list[float] = []
            errors: list = []

            def worker(tid: int) -> None:
                try:
                    from collections import deque

                    rng = np.random.default_rng(tid)
                    slots = rng.integers(0, parts, size=per_thread)
                    pending: deque = deque()
                    for slot in slots:
                        while len(pending) >= window:
                            fut, ts = pending.popleft()
                            fut.result(timeout=60)
                            lats.append(time.perf_counter() - ts)
                        pending.append((
                            dp.submit_append(int(slot), [PAYLOAD]),
                            time.perf_counter(),
                        ))
                    while pending:
                        fut, ts = pending.popleft()
                        fut.result(timeout=60)
                        lats.append(time.perf_counter() - ts)
                except Exception as e:  # a dead thread must fail the
                    errors.append((tid, repr(e)))  # point, not skew it

            threads = [
                threading.Thread(target=worker, args=(i,), daemon=True)
                for i in range(submitters)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            assert not errors, f"curve submitters failed: {errors}"
            assert len(lats) == submitters * per_thread
            a = np.asarray(lats) * 1e3
            curve.append({
                **pt,
                "offered_producers": submitters,
                "appends_per_sec": round(len(lats) / dt, 1),
                "p50_ack_ms": round(float(np.percentile(a, 50)), 3),
                "p99_ack_ms": round(float(np.percentile(a, 99)), 3),
                "rounds_per_dispatch": round(
                    dp.rounds / max(1, dp.dispatches), 2),
            })
        finally:
            dp.stop()
    return curve


def _run_spmd_parity(chain: int = 8, launches: int = 240) -> dict:
    """Dispatch parity: the production SPMD binding (shard_map over a
    device mesh) vs the local binding (vmap) on the SAME single chip —
    a 1x1 mesh with replicas=1, at the headline round shape, measured
    with the SAME sustained method as the headline. Proves the spmd
    binding's device program loses nothing before anyone trusts it on a
    pod slice (multi-chip semantics are covered by the virtual-mesh
    tests and dryrun_multichip; this is the single-chip-provable
    slice).

    The spmd arm runs the FUSED control binding — the one production
    runs now that make_spmd_fns honors fused_control (ISSUE 6) — with
    the legacy-control shard_map binding kept as a recorded A/B arm
    (`spmd_legacy_appends_per_sec`); `delta_pct` stays spmd-vs-local so
    the trajectory's r5 figure remains comparable.

    Inputs are COMMITTED to each binding's expected sharding before the
    timed window (for the 1x1 mesh, fully replicated NamedSharding).
    Passing device arrays with unspecified sharding instead makes every
    call re-resolve shardings on the python dispatch path — measured
    -12% on the spmd side ONLY, a bench artifact production never pays
    (the broker hands the bindings fresh host numpy arrays, which both
    bindings ingest identically). r4's +1.29% figure hid the same
    artifact differently: its burst windows were dominated by a fixed
    window cost shared by both bindings (PROFILE.md r5)."""
    import dataclasses

    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as _P

    from ripplemq_tpu.core.config import EngineConfig
    from ripplemq_tpu.core.encode import build_step_input
    from ripplemq_tpu.parallel.engine import make_local_fns, make_spmd_fns
    from ripplemq_tpu.parallel.mesh import make_mesh

    cfg = EngineConfig(
        partitions=1024, replicas=1, slots=12352, slot_bytes=128,
        max_batch=256, read_batch=32, max_consumers=64, max_offset_updates=8,
    )
    cfg_fused = dataclasses.replace(cfg, fused_control=True)
    B = cfg.max_batch
    one = build_step_input(cfg, appends={p: [PAYLOAD] * B
                                         for p in range(cfg.partitions)},
                           leader=0, term=1)
    chained = jax.tree.map(
        lambda x: np.broadcast_to(x, (chain,) + x.shape).copy(), one
    )
    alive = np.ones((cfg.partitions, cfg.replicas), bool)
    quorum = np.ones((cfg.partitions,), np.int32)
    adv = chain * B
    mesh = make_mesh(1, 1)
    rep = NamedSharding(mesh, _P())  # 1x1 mesh: everything replicated
    bindings = {
        "local": (make_local_fns(cfg), None),
        "spmd": (make_spmd_fns(cfg_fused, mesh), rep),
        "spmd_legacy": (make_spmd_fns(cfg, mesh), rep),
    }
    # Throughput varies between measurement windows (the host's cores
    # are shared), which can swamp a single-shot A/B. ALTERNATE the
    # bindings across
    # trials and take each one's best: additive noise can only slow a
    # trial down, so per-binding maxima approximate the true costs under
    # near-identical conditions.
    staged = {}
    for name, (fns, shard) in bindings.items():
        put = (lambda x: jax.device_put(x, shard)) if shard is not None \
            else jax.device_put
        staged[name] = (put(chained), put(alive), put(quorum),
                        _stage_trims(cfg, adv, launches, put))
        _sustained_warmup(fns, *staged[name][:3], staged[name][3])
    best = {name: 0.0 for name in bindings}
    for _ in range(4):
        for name, (fns, _) in bindings.items():
            inp, alive_d, quorum_d, trims = staged[name]
            rate, state = _sustained_window(
                fns, inp, alive_d, quorum_d, trims,
                launches * adv * cfg.partitions,
            )
            best[name] = max(best[name], rate)
            del state
    # Signed: positive = the production (spmd) binding is FASTER than
    # the local binding. R=1 is the WORST CASE for this delta: with no
    # replica write work to amortize it, the binding's fixed per-round
    # overhead (~70 us/launch host dispatch + the output-gather psum
    # machinery, measured r5) is fully exposed — ~-13% here bounds a
    # proportionally smaller cost at the R=5 production shape, where
    # write work dominates the round. Trust criterion: delta_pct > -20
    # at this maximally-exposed shape (PROFILE.md r5).
    delta = (best["spmd"] - best["local"]) / best["local"]
    fused_delta = (best["spmd"] - best["spmd_legacy"]) / best["spmd_legacy"]
    return {
        "local_appends_per_sec": round(best["local"], 1),
        "spmd_appends_per_sec": round(best["spmd"], 1),
        "spmd_binding": "fused_control",
        "spmd_legacy_appends_per_sec": round(best["spmd_legacy"], 1),
        "fused_vs_legacy_spmd_delta_pct": round(100 * fused_delta, 2),
        "delta_pct": round(100 * delta, 2),
    }


def _run_spmd_scaling(device_counts: tuple[int, ...] = (1, 2, 4, 8),
                      chain: int = 8, launches: int = 24,
                      windows: int = 2) -> dict:
    """Per-device-count scaling curve for the production (fused) SPMD
    binding: sustained committed appends/s with partitions sharded over
    the "part" mesh axis at 1/2/4/8 devices — one SUBPROCESS per count
    on a virtual CPU mesh (XLA_FLAGS device-count forcing, the same
    technique as __graft_entry__.dryrun_multichip, so it runs
    identically whether the parent bench sits on a TPU or a CPU host).
    Each point is the SAME sustained best-of-N method as the headline:
    the child (profiles/spmd_scaling.py --inner) imports
    _sustained_window/_stage_trims from this module and tail-verifies
    the ring after its best window.

    HONESTY: the virtual devices share ONE host's FLOPs and memory
    bandwidth, so this curve measures what sharding COSTS (collective,
    dispatch, and output-gather overhead as the mesh widens) — not what
    added silicon buys. A flat-ish curve means the sharded program
    wastes nothing; the real speedup curve needs a pod slice (the
    ROADMAP's carried v5e visit runs profiles/spmd_scaling.py there)."""
    import os
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "profiles", "spmd_scaling.py")
    points = []
    for n in device_counts:
        flags = os.environ.get("XLA_FLAGS", "")
        flags = " ".join(
            f for f in flags.split()
            if "xla_force_host_platform_device_count" not in f
        )
        env = dict(
            os.environ,
            XLA_FLAGS=(
                f"{flags} --xla_force_host_platform_device_count={n}"
            ).strip(),
            JAX_PLATFORMS="cpu",
        )
        res = subprocess.run(
            [sys.executable, script, "--inner", str(n),
             "--chain", str(chain), "--launches", str(launches),
             "--windows", str(windows)],
            env=env, capture_output=True, text=True,
        )
        if res.returncode != 0:
            raise RuntimeError(
                f"spmd_scaling devices={n} failed rc={res.returncode}: "
                f"{res.stderr[-2000:]}"
            )
        line = [ln for ln in res.stdout.splitlines()
                if ln.startswith("{")][-1]
        points.append(json.loads(line))
    base = points[0]["appends_per_sec"]
    return {
        "config": (f"P={points[0]['partitions']} R=1 "
                   f"B={points[0]['max_batch']} chain={chain} sustained "
                   f"fused-spmd, partitions sharded over 'part'"),
        "method": ("one subprocess per device count on a virtual CPU "
                   "mesh; virtual devices share one host's FLOPs, so "
                   "this prices sharding overhead, not added silicon"),
        "points": points,
        "vs_1dev": {
            str(p["devices"]): round(p["appends_per_sec"] / base, 3)
            for p in points
        },
    }


def e2e_raw_config(ports: list[int], partitions: int = 1024,
                   host_workers: int = 1) -> dict:
    """The e2e topology's cluster config (shared with
    profiles/host_edge.py, whose decomposition must measure the SAME
    shape the bench runs — a copied dict drifts). `host_workers` > 1
    boots the multi-core host plane (parallel/hostplane.py) on every
    broker — the host_plane_scaling phase's sweep axis."""
    return {
        "host_workers": host_workers,
        "brokers": [{"id": i, "host": "127.0.0.1", "port": p}
                    for i, p in enumerate(ports)],
        "topics": [{"name": "bench", "partitions": partitions,
                    "replication_factor": 3}],
        # Engine sized to the SYSTEM it measures: R=3 replica slots — the
        # topology's actual replication factor (3 brokers, topic RF 3;
        # the R=5 headline shape belongs to the engine-only rows, where
        # it is measured as such) — and a ring deep enough that trim
        # rides comfortably behind the store (the e2e run pushes ~2k
        # rows/partition). Oversizing either just burns host RAM
        # bandwidth on a low-core bench host and adds variance.
        # read_batch 1024: the consume phase drains through the host
        # mirror, which serves up to read_batch rows per call; the
        # auto-commit quorum rounds ride the pipelined commit path
        # (client/consumer.py prefetch) behind the drain.
        # fused_control/packed_writes: the PR 1 levers, on at the
        # operating point the bench ships (A/B'd in control_fusion_ab);
        # settle_window: the PR 3 pipelined-settle window (A/B: 1 =
        # legacy serialized settle).
        "engine": {
            "partitions": partitions, "replicas": 3, "slots": 4608,
            "slot_bytes": 128, "max_batch": 512, "read_batch": 1024,
            "max_consumers": 64, "max_offset_updates": 8,
            "fused_control": True, "packed_writes": True,
            "settle_window": 8,
        },
        "election_timeout_s": 0.5,
        # Generous liveness horizon: the bench saturates every core, and
        # a starved heartbeat thread must read as load, not death — a
        # mid-run metadata election deposes the controller and turns a
        # throughput measurement into a failover drill (observed on a
        # 2-core host at 1.5 s).
        "metadata_election_timeout_s": 8.0,
        "membership_poll_s": 0.5,
        "rpc_timeout_s": 60.0,   # a queued append must outlive a backlog
        # Workers block on round futures (ClusterConfig.rpc_workers), so
        # the pool must cover the full offered concurrency: in-flight
        # produce batches PLUS the drain's pipelined commits — 64 was
        # the produce throughput cap (64 parked handlers = no worker
        # free for the next frame; measured as acks pacing to the pool).
        "rpc_workers": 320,
        # Throughput operating point (the operating_curve documents the
        # latency cost): gather ~coalesce_s of burst per dispatch. Every
        # dispatch pays a fixed cost down the WHOLE pipeline (launch,
        # resolve, settle-entry, store framing, mirror bookkeeping), so
        # at saturation fewer-but-fuller dispatches win throughput
        # (PROFILE.md "host path").
        "coalesce_s": 0.03,
    }


# The stage histograms that make up the host-path decomposition
# (PROFILE.md "host path") — each produce ack's time, attributed live by
# the telemetry plane instead of hand-profiled: device launch, launch →
# committed fetch, commit → settle-window entry, the standby-ack
# barrier, local persist (with store append/fsync below it), and the
# whole dispatch → ack-release round trip; plus the batching factors
# (chain rounds per dispatch, replication rounds per group-commit frame).
_DECOMPOSITION_STAGES = (
    "engine.dispatch_us",
    "settle.commit_wait_us",
    "settle.enter_wait_us",
    "settle.standby_ack_us",
    "settle.persist_us",
    "settle.release_us",
    "store.append_us",
    "store.fsync_us",
    "repl.frame_us",
    "repl.group_rounds",
    "engine.chain_rounds",
)


def _print_tails(paths, nbytes: int = 2000) -> None:
    """A failed phase shows what its child processes said last (their
    stderr is kept on disk, never sent to DEVNULL)."""
    import os
    import sys

    for path in paths:
        try:
            with open(path, "rb") as f:
                tail = f.read()[-nbytes:].decode("utf-8", "replace")
        except OSError:
            continue
        if tail.strip():
            print(f"--- {os.path.basename(path)} (tail) ---\n{tail}",
                  file=sys.stderr, flush=True)


def _proc_cpu_s(pid: int) -> float:
    """utime+stime of one live process from /proc/<pid>/stat, seconds
    (Linux; 0.0 anywhere it can't be read) — the e2e bench's honest
    per-process CPU decomposition (PROFILE.md round 12): on a GIL-bound
    host path, WHERE the interpreter seconds land is the measurement
    that says whether a topology knob moved work off the broker."""
    try:
        import os

        with open(f"/proc/{pid}/stat", "rb") as f:
            fields = f.read().rsplit(b") ", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
    except Exception:
        return 0.0


def _latency_decomposition(metrics_snapshot: dict) -> dict:
    """The per-stage summaries (count/mean/p50/p90/p99/max, integer
    microseconds for the *_us stages) pulled out of an admin.metrics
    snapshot — the live-measured version of PROFILE.md's host-path
    table."""
    hists = metrics_snapshot.get("histograms", {})
    return {k: hists[k] for k in _DECOMPOSITION_STAGES if k in hists}


def _e2e_client_main(spec_path: str) -> None:
    """CLIENT-SUBPROCESS entry (`python bench.py _e2e_client spec.json`):
    the e2e producer/consumer loadgen, moved OUT of the controller
    process (ISSUE 12) so client interpreter CPU — codec encode, socket
    writes, window bookkeeping, ~half of PROFILE.md's measured 28 µs/msg
    wall — stops being billed to the broker's GIL. One proc runs
    `threads` windowed producer threads (and the same count of
    drainers); the parent drives phases over a stdin/stdout line
    protocol (PRODUCE / DRAIN <phase> / EXIT → RESULT <json>), so
    process boot and import cost land OUTSIDE every timed window and
    producer sequence counters persist across phases (count-exactness
    is cumulative)."""
    import sys
    import threading
    from collections import deque

    from ripplemq_tpu.client.consumer import ConsumerClient
    from ripplemq_tpu.client.producer import ProducerClient

    with open(spec_path) as f:
        spec = json.load(f)
    bootstrap = spec["bootstrap"]
    threads = int(spec["threads"])
    batch = int(spec["batch"])
    window = int(spec["window"])
    duration_s = float(spec["duration_s"])
    partitions = int(spec["partitions"])
    read_batch = int(spec["read_batch"])
    proc_id = int(spec["proc_id"])
    nprocs = int(spec["nprocs"])
    total_threads = nprocs * threads

    pc = ProducerClient(bootstrap, rpc_timeout_s=120.0)
    seqs = [0] * threads

    def produce_phase() -> dict:
        counts: dict = {}
        errors: list = []
        t0 = time.monotonic()
        stop_at = t0 + duration_s

        def producer(tid: int) -> None:
            try:
                _producer(tid)
            except Exception as e:  # a dead thread must FAIL the
                errors.append((tid, repr(e)))  # bench, not deflate it

        def _producer(tid: int) -> None:
            acked = nbytes = 0
            seq = seqs[tid]
            gtid = proc_id * threads + tid  # global payload namespace
            pending: deque = deque()

            def land(w, n, nb):
                nonlocal acked, nbytes
                w()
                acked += n
                nbytes += nb

            while time.monotonic() < stop_at:
                while len(pending) >= window:
                    land(*pending.popleft())
                payloads = []
                for _ in range(batch):
                    head = b"e2e-%d-%08d|" % (gtid, seq)
                    seq += 1
                    payloads.append(head.ljust(100, b"x"))
                nb = sum(map(len, payloads))
                w = pc.produce_batch_async("bench", payloads)
                pending.append((w, batch, nb))
            while pending:
                land(*pending.popleft())
            seqs[tid] = seq
            counts[tid] = (acked, nbytes)

        workers = [
            threading.Thread(target=producer, args=(i,), daemon=True)
            for i in range(threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        secs = time.monotonic() - t0
        if errors:
            raise AssertionError(f"producer threads failed: {errors}")
        assert len(counts) == threads
        return {"acked": sum(v[0] for v in counts.values()),
                "nbytes": sum(v[1] for v in counts.values()),
                "secs": secs}

    def drain_phase(phase: int) -> dict:
        drained = [0] * threads
        dbytes = [0] * threads
        warmups = [0] * threads
        cerrors: list = []

        def drainer(tid: int) -> None:
            gtid = proc_id * threads + tid
            cc = ConsumerClient(bootstrap, f"e2e-drain-{phase}-{gtid}",
                                max_messages=read_batch,
                                rpc_timeout_s=60.0, prefetch=1)
            try:
                for p in range(gtid, partitions, total_threads):
                    while True:
                        msgs, _, _, _ = cc.consume_with_position(
                            "bench", partition=p)
                        if not msgs:
                            break  # commit-bounded: caught up
                        drained[tid] += len(msgs)
                        dbytes[tid] += sum(map(len, msgs))
                        warmups[tid] += sum(
                            m.startswith(b"e2e-warmup") for m in msgs
                        )
            except Exception as e:  # a dead drainer FAILS the bench
                cerrors.append((tid, repr(e)))
            finally:
                cc.close()

        drainers = [
            threading.Thread(target=drainer, args=(i,), daemon=True)
            for i in range(threads)
        ]
        ct0 = time.monotonic()
        for d in drainers:
            d.start()
        for d in drainers:
            d.join()
        csecs = time.monotonic() - ct0
        if cerrors:
            raise AssertionError(f"consumer threads failed: {cerrors}")
        return {"drained": sum(drained), "dbytes": sum(dbytes),
                "warmups": sum(warmups), "secs": csecs}

    print("READY", flush=True)
    try:
        for line in sys.stdin:
            cmd = line.split()
            if not cmd:
                continue
            if cmd[0] == "PRODUCE":
                res = produce_phase()
            elif cmd[0] == "DRAIN":
                res = drain_phase(int(cmd[1]))
            elif cmd[0] == "EXIT":
                break
            else:
                raise AssertionError(f"unknown command {cmd!r}")
            print("RESULT " + json.dumps(res), flush=True)
    except Exception as e:
        print("ERROR " + repr(e), flush=True)
        raise
    finally:
        pc.close()


def _run_e2e(duration_s: float = 12.0, n_brokers: int = 3,
             threads: int = 8, batch: int = 512, window: int = 16,
             phases: int = 2, obs: bool = True, host_workers: int = 1,
             client_procs: int = 2) -> dict:
    """END-TO-END produce throughput: fresh, distinct payloads streamed
    by real producer clients through TCP sockets, broker dispatch, the
    DataPlane batcher, device quorum rounds, the round store, AND the
    standby replication stream — nothing resident-input-replayed. This
    is the number the reference's implied metric means (its path IS its
    socket path, mq-common/.../PartitionClient.java:31-59; SURVEY.md §6).

    Topology: a 3-broker cluster (controller + 2 replication standbys)
    over real loopback TCP — the controller in this process (the bench
    warms its programs and audits its engine counters), each standby a
    REAL broker process via the CLI entry, as deployed (the reference's
    docker-compose shape). Partition leaders collocate on the controller
    (manager.plan_elections prefers the engine host on log ties), so
    producers talk straight to the broker that owns the device program,
    as a single-chip deployment would be configured.

    Offered load: `threads` windowed producers keeping `window` batches
    in flight each (recorded as e2e_offered_batches). The window is
    sized to SATURATE the host path — the per-dispatch device cost is
    mostly fixed (PROFILE.md "host path"), so throughput is set by how
    many batches each dispatch can carry; a shallow window measures the
    client's window, not the broker. The figure remains a low-core-host
    floor, not a ceiling, for real deployments.

    The producer/consumer clients run in `client_procs` SUBPROCESSES
    (`_e2e_client_main`) so their interpreter CPU never shares the
    controller's GIL; `host_workers` > 1 additionally boots the
    multi-core host plane on every broker (the host_plane_scaling
    sweep's axis)."""
    import os
    import shutil
    import socket
    import subprocess
    import sys
    import tempfile

    import yaml

    from ripplemq_tpu.broker.server import BrokerServer
    from ripplemq_tpu.metadata.cluster_config import parse_cluster_config

    socks = [socket.socket() for _ in range(n_brokers)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()

    partitions = 1024
    raw = e2e_raw_config(ports, partitions, host_workers=host_workers)
    raw["obs"] = obs  # telemetry A/B knob (PROFILE.md overhead table)
    tmp = tempfile.mkdtemp(prefix="rmq-e2e-")
    config = parse_cluster_config(raw)
    brokers = []
    procs: list = []
    # One process per chip: THIS process holds it (the controller below),
    # so every child — standby brokers and client loadgens — is pinned
    # to the CPU backend from outside; left to inherit, a standby's
    # first JAX use (sealed-segment erasure) would try to take the chip
    # and fail or hang. Their stderr is kept on disk and its tail
    # printed if the phase fails.
    child_env = dict(os.environ, JAX_PLATFORMS="cpu")
    err_paths: list[str] = []

    def _stderr_file(name: str):
        err_paths.append(os.path.join(tmp, f"{name}.stderr"))
        return open(err_paths[-1], "wb")

    try:
        # The CONTROLLER runs in this process (the bench reads its engine
        # counters and warms its programs); the standby brokers run as
        # REAL PROCESSES via the CLI entry — the deployment shape (one
        # process per broker, like the reference's docker-compose), and
        # on a low-core host it keeps the standby side's replication
        # work (frame decode, store framing, acks) off the controller
        # interpreter's GIL, which a single-process topology measured as
        # a hard ceiling on the produce path.
        controller = BrokerServer(0, config, net=None,
                                  data_dir=os.path.join(tmp, "d0"))
        controller.start()
        brokers.append(controller)
        cfg_path = os.path.join(tmp, "cluster.yaml")
        with open(cfg_path, "w") as f:
            yaml.safe_dump(raw, f)
        for i in range(1, n_brokers):
            with _stderr_file(f"broker-{i}") as errf:
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "ripplemq_tpu.broker",
                     "--id", str(i), "--config", cfg_path,
                     "--data-dir", tmp, "--log-level", "WARNING"],
                    stdout=subprocess.DEVNULL, stderr=errf, env=child_env,
                ))

        from ripplemq_tpu.client.consumer import ConsumerClient
        from ripplemq_tpu.client.metadata import MetadataManager
        from ripplemq_tpu.client.producer import ProducerClient
        from ripplemq_tpu.wire.transport import TcpClient

        bootstrap = [f"127.0.0.1:{p}" for p in ports]
        transport = TcpClient()
        meta = MetadataManager(transport, bootstrap,
                               refresh_interval_s=3600, rpc_timeout_s=5.0)
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            try:
                meta.refresh()
                t = meta.topic("bench")
                if (t is not None and t.assignments
                        and all(a.leader is not None
                                for a in t.assignments)):
                    break
            except Exception:
                pass
            time.sleep(0.5)
        else:
            raise AssertionError("e2e cluster never elected all leaders")
        meta.close()
        transport.close()

        # Compile every active-set bucket the wave can hit, then wait out
        # the boot-time background warm too — a multi-second XLA compile
        # landing inside the timed window steals CPU AND the device lock
        # from live dispatches (sampled in the e2e profile).
        controller.dataplane.warm(
            buckets=controller.dataplane.all_buckets()
        )
        wt = getattr(controller, "_warm_thread", None)
        if wt is not None:
            wt.join(timeout=600)
        pc = ProducerClient(bootstrap, rpc_timeout_s=120.0)
        pc.produce_batch("bench", [b"e2e-warmup"] * 8)
        pc.close()
        dp = controller.dataplane
        standby_procs = list(procs)
        cpu_self0 = _proc_cpu_s(os.getpid())

        # CLIENT SUBPROCESSES (ISSUE 12): the producer/consumer loadgen
        # runs in `client_procs` dedicated processes (`python bench.py
        # _e2e_client spec.json`, a jax-free import chain) so client
        # interpreter CPU — codec encode, socket writes, window
        # bookkeeping — stops sharing the controller's GIL. Before this
        # split the clients' ~half of the measured 28 µs/msg host wall
        # was billed straight to the broker (PROFILE.md round 12 has the
        # measured delta). The parent drives phases over a line
        # protocol; boot/import cost lands outside every timed window.
        tpp = max(1, threads // max(1, client_procs))
        clients = []
        for i in range(client_procs):
            spec = {
                "bootstrap": bootstrap, "proc_id": i,
                "nprocs": client_procs, "threads": tpp,
                "batch": batch, "window": window,
                "duration_s": duration_s, "partitions": partitions,
                "read_batch": raw["engine"]["read_batch"],
            }
            spec_path = os.path.join(tmp, f"client{i}.json")
            with open(spec_path, "w") as f:
                json.dump(spec, f)
            with _stderr_file(f"client-{i}") as errf:
                c = subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__),
                     "_e2e_client", spec_path],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    stderr=errf, text=True, bufsize=1, env=child_env,
                )
            clients.append(c)
            procs.append(c)  # the teardown path covers a failed run

        def _expect(c, tag: str) -> str:
            line = (c.stdout.readline() or "").strip()
            assert line.startswith(tag), (
                f"e2e client answered {line!r}, wanted {tag}"
            )
            return line[len(tag):].strip()

        for c in clients:
            _expect(c, "READY")

        def client_phase(cmd: str) -> list[dict]:
            for c in clients:
                c.stdin.write(cmd + "\n")
                c.stdin.flush()
            return [json.loads(_expect(c, "RESULT ")) for c in clients]

        # Best-of-N phases: produce window then full drain, repeated.
        # Same methodology as _run_sustained's best-of-N windows —
        # additive noise (this class of bench host shows >2x run-to-run
        # swings from hypervisor scheduling) only ever slows a phase, so
        # per-phase maxima bound the system's actual capacity. Counts
        # stay exact across phases: sequences continue (in each client
        # proc's memory), and every drain re-reads the FULL topic from
        # offset 0 under fresh consumer ids, so phase k's drain must
        # equal the cumulative ack count.
        acked_total = 0
        nbytes_total = 0
        best_produce = (0.0, 0.0)  # (appends/s, MB/s)
        best_consume = (0.0, 0.0)
        consume_secs = 0.0
        consumed_final = 0
        produce_secs = 0.0

        for phase in range(max(1, phases)):
            # The phase window is each client's own measured duration;
            # the clients start within the protocol write loop (~ms
            # skew), so max() is the honest concurrent-window length.
            outs = client_phase("PRODUCE")
            acked = sum(o["acked"] for o in outs)
            nbytes = sum(o["nbytes"] for o in outs)
            secs = max(o["secs"] for o in outs)
            assert acked > 0
            acked_total += acked
            nbytes_total += nbytes
            produce_secs += secs
            best_produce = max(best_produce,
                               (acked / secs, nbytes / secs / 1e6))
            # The controller's committed-entry count must cover every ack.
            assert dp is not None and dp.committed_entries >= acked_total
            # END-TO-END consume: the client procs' drainer threads pull
            # the WHOLE topic over TCP — socket → dispatch → host-mirror
            # (or host-plane worker mirror) read → codec, prefetch=1
            # keeping the next window's fetch in flight and auto-commits
            # pipelined behind the drain (client/consumer.py readahead).
            douts = client_phase(f"DRAIN {phase}")
            consumed = sum(o["drained"] for o in douts)
            cbytes = sum(o["dbytes"] for o in douts)
            nwarm = sum(o["warmups"] for o in douts)
            csecs = max(o["secs"] for o in douts)
            consume_secs += csecs
            consumed_final = consumed
            # Count honesty: every async-acked append must come back
            # exactly once (the async path re-sends only after a
            # not_leader REFUSAL, which never appends — so no
            # duplicates; warmup produce_batch CAN retry, hence counted
            # apart). Each drain covers the topic SO FAR, so it must
            # equal the cumulative acks.
            assert consumed - nwarm == acked_total, (consumed, acked_total)
            best_consume = max(best_consume,
                               (consumed / csecs, cbytes / csecs / 1e6))

        # Per-process CPU decomposition (collected while every process
        # is still alive): where the interpreter seconds of this run
        # actually landed. `controller` is THIS process minus the
        # pre-run baseline (boot/warm excluded); worker CPU is listed
        # apart so the host-plane arms show what moved off the broker's
        # GIL vs what the extra hop cost.
        def _child_pids(ppid: int) -> list[int]:
            import glob

            out = []
            for st in glob.glob("/proc/[0-9]*/stat"):
                try:
                    with open(st, "rb") as f:
                        rest = f.read().rsplit(b") ", 1)[1].split()
                    if int(rest[1]) == ppid:
                        out.append(int(st.split("/")[2]))
                except Exception:
                    continue
            return out

        hp = controller.hostplane
        cpu_decomp = {
            "controller_s": round(_proc_cpu_s(os.getpid()) - cpu_self0, 1),
            "controller_workers_s": round(sum(
                _proc_cpu_s(p) for p in (hp.worker_pids() if hp else [])
            ), 1),
            "standbys_s": round(sum(
                _proc_cpu_s(p.pid) + sum(_proc_cpu_s(c)
                                         for c in _child_pids(p.pid))
                for p in standby_procs
            ), 1),
            "clients_s": round(sum(_proc_cpu_s(c.pid) for c in clients), 1),
        }

        for c in clients:
            c.stdin.write("EXIT\n")
            c.stdin.flush()
        for c in clients:
            c.wait(timeout=30)

        # Readback honesty: consume a window back through the client SDK
        # and check the loadgen payload structure survived byte-exact.
        cc = ConsumerClient(bootstrap, "e2e-verify", rpc_timeout_s=60.0)
        checked = 0
        for _ in range(40):
            for m in cc.consume("bench"):
                if m.startswith(b"e2e-warmup"):
                    continue
                head, _, pad = m.partition(b"|")
                tag, tid, seq = head.split(b"-")
                assert tag == b"e2e" and tid.isdigit() and seq.isdigit(), m[:24]
                assert pad == b"x" * len(pad) and len(m) == 100, m[:24]
                checked += 1
            if checked >= 256:
                break
        assert checked >= 256, f"only {checked} messages read back"
        cc.close()

        settle = dp.settle_stats()
        # End-of-run telemetry snapshot: the BENCH_r*.json artifact
        # carries the full decomposition, not just totals — the obs
        # plane's metrics are the same admin.metrics every broker serves.
        from ripplemq_tpu.wire import codec as _codec

        metrics_snap = controller.metrics.snapshot()
        return {
            "e2e_obs": obs,
            "latency_decomposition": _latency_decomposition(metrics_snap),
            "admin_metrics": {
                "metrics": metrics_snap,
                "wire": _codec.codec_stats(),
            },
            "e2e_appends_per_sec": round(best_produce[0], 1),
            "e2e_mb_per_sec": round(best_produce[1], 2),
            "e2e_acked": acked_total,
            "e2e_offered_batches": client_procs * tpp * window,
            "e2e_client_procs": client_procs,
            "e2e_host_workers": host_workers,
            "e2e_cpu_decomposition": cpu_decomp,
            "e2e_phases": max(1, phases),
            "e2e_seconds": round(produce_secs, 1),
            "e2e_readback": "verified",
            "e2e_consume_msgs_per_sec": round(best_consume[0], 1),
            "e2e_consume_mb_per_sec": round(best_consume[1], 2),
            "e2e_consumed": consumed_final,
            "e2e_consume_seconds": round(consume_secs, 1),
            "e2e_consume_verified": "count-exact",
            # Settle-pipeline occupancy on the controller across the run
            # (window width, mean depth at enqueue, backpressure hits) —
            # the pipelined-settle lever's visibility in the trajectory.
            "settle_pipeline": settle,
        }
    except BaseException:
        _print_tails(err_paths)
        raise
    finally:
        for b in brokers:
            b.stop()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def _run_host_plane_scaling(worker_counts: tuple[int, ...] = (1, 2, 4),
                            duration_s: float = 6.0,
                            phases: int = 2) -> dict:
    """ISSUE 12 tentpole: same-host worker-count sweep of the multi-core
    host plane. Each arm runs the FULL e2e topology (subprocess standby
    brokers, subprocess clients, real TCP) with `host_workers` worker
    subprocesses per broker — workers=1 is the single-process host path,
    the pre-PR-12 shape — using the same best-of-N sustained method and
    the same count-exact readback as the headline e2e phase, in ONE run
    on one host so the arms share their noise floor. The verdict
    carries every arm plus scaling_x = best/workers-1; `host_cores`
    records the parallelism physically available (on a 2-core container
    the curve prices the plane's overhead, not its headroom — the ≥4-core
    reading is the refactor's target, PROFILE.md round 12)."""
    import os

    arms = []
    for w in worker_counts:
        r = _run_e2e(duration_s=duration_s, phases=phases, host_workers=w)
        arms.append({
            "host_workers": w,
            "appends_per_sec": r["e2e_appends_per_sec"],
            "consume_msgs_per_sec": r["e2e_consume_msgs_per_sec"],
            "acked": r["e2e_acked"],
            "readback": r["e2e_consume_verified"],
            "cpu_decomposition": r["e2e_cpu_decomposition"],
        })
    base = arms[0]["appends_per_sec"]
    best = max(arms, key=lambda a: a["appends_per_sec"])
    return {
        "arms": arms,
        "baseline_appends_per_sec": base,
        "best_workers": best["host_workers"],
        "best_appends_per_sec": best["appends_per_sec"],
        "scaling_x": round(best["appends_per_sec"] / base, 2),
        "host_cores": os.cpu_count(),
    }


def _run_group_consume(n_groups: int = 3, members: int = 2,
                       partitions: int = 4, n_msgs: int = 600) -> dict:
    """Multi-group drain (ISSUE 7): `n_groups` consumer groups, each of
    `members` GroupConsumer members, independently drain the same
    produced topic — the multi-tenant fan-out workload the group
    coordinator opens (every group re-reads the full log through its
    own shared offsets). COUNT-EXACT per group: a group finishing with
    anything but exactly `n_msgs` delivered fails the bench. Runs on an
    in-proc cluster (the coordinator + fencing + shared-offset path is
    the subject; the TCP frame cost is e2e's)."""
    import threading as _threading

    from ripplemq_tpu.chaos.cluster import InProcCluster, make_cluster_config
    from ripplemq_tpu.client import GroupConsumer, ProducerClient
    from ripplemq_tpu.metadata.models import Topic

    config = make_cluster_config(
        3, topics=(Topic("gbench", partitions, 3),),
        engine=None,
    )
    with InProcCluster(config) as cluster:
        cluster.wait_for_leaders()
        bootstrap = [b.address for b in config.brokers]
        producer = ProducerClient(
            bootstrap, transport=cluster.client("gbench-p"),
            rpc_timeout_s=10.0,
        )
        per_part = n_msgs // partitions
        n_msgs = per_part * partitions
        B = config.engine.max_batch
        for pid in range(partitions):
            payloads = [b"g-%d-%06d" % (pid, i) for i in range(per_part)]
            for i in range(0, per_part, B):
                producer.produce_batch("gbench", payloads[i : i + B],
                                       partition=pid)
        producer.close()

        counts = {g: 0 for g in range(n_groups)}
        lock = _threading.Lock()
        stop = _threading.Event()

        def member(gi: int, mi: int):
            gc = GroupConsumer(
                bootstrap, f"bg{gi}", topics=["gbench"],
                member_id=f"m{mi}",
                transport=cluster.client(f"gbench-{gi}-{mi}"),
                heartbeat_s=0.5, rpc_timeout_s=10.0,
            )
            try:
                gc.join()
                while not stop.is_set():
                    _, msgs = gc.poll(max_messages=64)
                    if msgs:
                        with lock:
                            counts[gi] += len(msgs)
                    with lock:
                        if counts[gi] >= n_msgs:
                            return
            finally:
                gc.close()

        threads = [
            _threading.Thread(target=member, args=(gi, mi), daemon=True)
            for gi in range(n_groups) for mi in range(members)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        deadline = time.time() + 120
        while time.time() < deadline:
            with lock:
                if all(v >= n_msgs for v in counts.values()):
                    break
            time.sleep(0.05)
        elapsed = time.perf_counter() - t0
        stop.set()
        for t in threads:
            t.join(timeout=10)
        exact = all(v == n_msgs for v in counts.values())
        if not exact:
            raise AssertionError(
                f"group drain not count-exact: wanted {n_msgs}/group, "
                f"got {counts} (duplicates or loss across the shared-"
                f"offset path)"
            )
        total = sum(counts.values())
        return {
            "e2e_group_consume_msgs_per_sec": round(total / elapsed, 1),
            "group_consume": {
                "groups": n_groups, "members_per_group": members,
                "partitions": partitions, "msgs_per_group": n_msgs,
                "elapsed_s": round(elapsed, 3), "count_exact": exact,
            },
        }


def _run_control_plane_storm(
    shapes: tuple[tuple[int, int, int], ...] = (
        (10, 10, 4),      # 100 members — also run as the direct baseline
        (40, 10, 8),      # 400 members
        (100, 10, 16),    # 1000 members / 100 groups — the headline shape
    ),
    churn_rounds: int = 2,
    churn_frac: float = 0.2,
    beat_window_s: float = 1.5,
) -> dict:
    """Control-plane volume sweep (ISSUE 18): group count x churn rate x
    tenant count, driving the membership RPC surface directly (the data
    plane is irrelevant here — no payloads move). Each shape storms
    `groups x members` group.join RPCs plus `tenants` producer.register
    RPCs through a thread pool, then `churn_rounds` rounds of
    leave+rejoin over `churn_frac` of the membership, then a fixed
    heartbeat window with every member beating.

    Reported per shape (read from the brokers' admin.stats
    `control_plane` block — the same counters operators see):

    - raft proposals per membership EVENT: with wave batching every
      coalesced OP_BATCH is ONE proposal carrying many events; the
      collapse factor (events/proposals) is the tentpole claim (>= 20x
      at the 1000-member shape). The direct arm (meta_batch_s=0, the
      pre-wave path) is 1 proposal/event BY CONSTRUCTION — measured on
      the smallest shape to keep the bench bounded.
    - leader heartbeat RPCs/s BEFORE vs AFTER: before = the measured
      member beat arrival rate (every one of which the old path
      forwarded to the metadata leader); after = the measured
      group.beats frame ingest rate at the leader (O(brokers) per
      relay interval, heartbeat_relay_s).
    - convergence p50/p99: per membership event, the RPC round-trip
      until the proposing broker serves the new replicated state (wave
      wait + raft commit + local apply — the latency a joining member
      actually experiences)."""
    import queue as _queue
    import random
    import threading as _threading

    from ripplemq_tpu.chaos.cluster import InProcCluster, make_cluster_config
    from ripplemq_tpu.metadata.models import Topic

    partitions = 8

    def one_arm(groups: int, members: int, tenants: int,
                meta_batch_s: float) -> dict:
        config = make_cluster_config(
            3, topics=(Topic("storm", partitions, 3),), engine=None,
            rpc_timeout_s=10.0,
            # Nobody beats during the join/churn storm: keep sessions
            # from lapsing so no eviction waves pollute the counters.
            group_session_timeout_s=30.0,
            meta_batch_s=meta_batch_s,
        )
        with InProcCluster(config) as cluster:
            cluster.wait_for_leaders()
            addrs = [b.address for b in config.brokers]
            n_workers = min(128, groups * members)
            clients = [cluster.client(f"storm-w{w}")
                       for w in range(n_workers)]
            lat_ms: list[float] = []
            lat_lock = _threading.Lock()
            work: _queue.Queue = _queue.Queue()
            errs: list[str] = []

            def worker(w: int):
                while True:
                    req = work.get()
                    if req is None:
                        work.task_done()
                        return
                    t0 = time.perf_counter()
                    try:
                        resp = clients[w].call(addrs[w % len(addrs)],
                                               req, timeout=15.0)
                        if resp.get("ok"):
                            with lat_lock:
                                lat_ms.append(
                                    (time.perf_counter() - t0) * 1e3)
                        else:
                            errs.append(str(resp.get("error")))
                    except Exception as e:
                        errs.append(f"{type(e).__name__}: {e}")
                    finally:
                        work.task_done()

            threads = [_threading.Thread(target=worker, args=(w,),
                                         daemon=True)
                       for w in range(n_workers)]
            for t in threads:
                t.start()

            def run_events(events: list[dict]) -> None:
                for ev in events:
                    work.put(ev)
                work.join()

            # --- the join storm: every member + every tenant pid ---
            joins = [
                {"type": "group.join", "group": f"sg{gi}",
                 "member": f"m{mi}", "topics": ["storm"]}
                for gi in range(groups) for mi in range(members)
            ]
            regs = [
                {"type": "producer.register", "name": f"t{k}/storm"}
                for k in range(tenants)
            ]
            n_events = 0
            before = len(lat_ms)
            run_events(joins + regs)
            n_events += len(joins) + len(regs)

            # --- churn rounds: churn_frac of members leave+rejoin ---
            rng = random.Random(1234)
            roster = [(gi, mi) for gi in range(groups)
                      for mi in range(members)]
            for _ in range(churn_rounds):
                sample = rng.sample(roster,
                                    max(1, int(len(roster) * churn_frac)))
                leaves = [
                    {"type": "group.leave", "group": f"sg{gi}",
                     "member": f"m{mi}"}
                    for gi, mi in sample
                ]
                run_events(leaves)
                rejoins = [
                    {"type": "group.join", "group": f"sg{gi}",
                     "member": f"m{mi}", "topics": ["storm"]}
                    for gi, mi in sample
                ]
                run_events(rejoins)
                n_events += len(leaves) + len(rejoins)
            assert len(lat_ms) - before + len(errs) >= n_events * 0.95, (
                f"storm lost events: {len(lat_ms)} acks, errors {errs[:5]}"
            )

            # --- heartbeat window: every member beats continuously ---
            stop = _threading.Event()
            beat_counts = [0] * n_workers

            def beater(w: int):
                mine = roster[w::n_workers]
                while not stop.is_set():
                    for gi, mi in mine:
                        if stop.is_set():
                            return
                        clients[w].call(
                            addrs[(w + gi) % len(addrs)],
                            {"type": "group.heartbeat",
                             "group": f"sg{gi}", "member": f"m{mi}"},
                            timeout=15.0,
                        )
                        beat_counts[w] += 1

            hb_before = _cp_stats(cluster, addrs)
            beaters = [_threading.Thread(target=beater, args=(w,),
                                         daemon=True)
                       for w in range(n_workers)]
            t0 = time.perf_counter()
            for t in beaters:
                t.start()
            time.sleep(beat_window_s)
            stop.set()
            for t in beaters:
                t.join(timeout=10)
            # Let the last relay frames flush before reading counters.
            time.sleep(config.heartbeat_relay_s * 2 + 0.1)
            window = time.perf_counter() - t0
            hb_after = _cp_stats(cluster, addrs)

            for _ in threads:
                work.put(None)
            for t in threads:
                t.join(timeout=5)

            stats = hb_after
            waves = stats["waves"]
            wave_events = stats["wave_events"]
            beats_issued = sum(beat_counts)
            # beat_frames counts FRAMES (one per broker per relay
            # interval — the leader's RPC load); beats_relayed counts
            # the per-member stamps those frames carried.
            frames = stats["beat_frames"] - hb_before["beat_frames"]
            proposals = waves if meta_batch_s > 0 else n_events
            arm = {
                "groups": groups, "members": groups * members,
                "tenants": tenants,
                "membership_events": n_events,
                "raft_proposals": proposals,
                "proposals_per_event": round(proposals / n_events, 4),
                "proposal_collapse": round(n_events / max(1, proposals),
                                           1),
                "wave_size_hist": stats["wave_size_hist"],
                "convergence_ms_p50": round(
                    float(np.percentile(lat_ms, 50)), 2),
                "convergence_ms_p99": round(
                    float(np.percentile(lat_ms, 99)), 2),
                # Before the relay plane every member beat was an RPC
                # ON THE LEADER; now the leader ingests O(brokers)
                # aggregated frames per relay interval.
                "leader_heartbeat_rpcs_per_s_before": round(
                    beats_issued / window, 1),
                "leader_heartbeat_rpcs_per_s_after": round(
                    frames / window, 1),
                "errors": len(errs),
            }
            return arm

    out: dict = {"shapes": []}
    g0, m0, t0_ = shapes[0]
    out["direct_baseline"] = one_arm(g0, m0, t0_, meta_batch_s=0.0)
    for groups, members, tenants in shapes:
        out["shapes"].append(one_arm(groups, members, tenants,
                                     meta_batch_s=0.05))
    out["headline"] = out["shapes"][-1]
    return {"control_plane_storm": out}


def _cp_stats(cluster, addrs: list[str]) -> dict:
    """Sum the `control_plane` admin.stats block across brokers (waves
    and events count where the proposing broker coalesced them; beat
    frames count where the leader ingested them)."""
    probe = cluster.client("storm-stats")
    total = {"waves": 0, "wave_events": 0, "beats_relayed": 0,
             "beat_frames": 0, "heartbeats_local": 0,
             "wave_size_hist": {}}
    for addr in addrs:
        try:
            st = probe.call(addr, {"type": "admin.stats"}, timeout=5.0)
        except Exception:
            continue
        cp = st.get("control_plane") or {}
        total["waves"] += int(cp.get("waves", 0))
        total["wave_events"] += int(cp.get("wave_events", 0))
        total["beats_relayed"] += int(cp.get("beats_relayed", 0))
        total["beat_frames"] += int(cp.get("beat_frames", 0))
        total["heartbeats_local"] += int(cp.get("heartbeats_local", 0))
        for k, v in (cp.get("wave_size_hist") or {}).items():
            total["wave_size_hist"][k] = (
                total["wave_size_hist"].get(k, 0) + int(v)
            )
    return total


def _run_consume_fanout(consumer_counts: tuple[int, ...] = (4, 16),
                        partitions: int = 2, n_msgs: int = 480) -> dict:
    """Fan-out consume A/B (ISSUE 16): C independent consumers each
    drain the SAME pre-produced log end to end — the multi-subscriber
    workload where every cursor historically funneled through one
    partition leader — with follower reads OFF vs ON, sweeping the
    consumer count. Each arm boots a fresh 3-broker PROCESS cluster
    (real TCP, one OS process per broker: the shape where serving
    reads from standbys buys actual CPU parallelism; in-proc brokers
    share one GIL and would price only the extra hop), produces the
    full log once, waits for the replication floors to settle on the
    standbys, then fans the consumers out. COUNT-EXACT per arm: every
    consumer must read exactly `n_msgs` rows (per-consumer offsets —
    each cursor is its own group re-reading the topic); anything else
    fails the bench. ON arms also report how many deliveries the
    followers actually served — an ON arm the leader quietly absorbed
    would otherwise read as a null A/B. `host_cores` records the
    parallelism physically available: like the host-plane sweep
    (PROFILE.md round 12), a 1–2 core container serializes the three
    broker processes onto one clock and the curve prices the plane's
    OVERHEAD (extra hop, refusal fallbacks); the ≥4-core reading is
    where spreading reads over standbys buys throughput."""
    import os as _os
    import shutil as _shutil
    import tempfile as _tempfile
    import threading as _threading

    from ripplemq_tpu.chaos.proc_cluster import (
        ProcCluster,
        free_ports,
        make_proc_cluster_config,
    )
    from ripplemq_tpu.client import ConsumerClient, ProducerClient
    from ripplemq_tpu.metadata.models import Topic

    per_part = n_msgs // partitions
    total_msgs = per_part * partitions

    def one_arm(consumers: int, follower: bool) -> dict:
        tmp = _tempfile.mkdtemp(prefix="fanout-")
        config = make_proc_cluster_config(
            free_ports(3), topics=(Topic("fanout", partitions, 3),),
            follower_reads=follower,
        )
        cluster = ProcCluster(config=config, data_dir=tmp)
        try:
            cluster.start()
            cluster.wait_for_leaders()
            deadline = time.time() + 120
            while time.time() < deadline and not cluster.controller_ready():
                time.sleep(0.1)
            bootstrap = [b.address for b in config.brokers]
            producer = ProducerClient(
                bootstrap, transport=cluster.client("fanout-p"),
                rpc_timeout_s=10.0,
            )
            B = config.engine.max_batch
            for pid in range(partitions):
                payloads = [b"f-%d-%06d" % (pid, i)
                            for i in range(per_part)]
                for i in range(0, per_part, B):
                    producer.produce_batch("fanout", payloads[i:i + B],
                                           partition=pid)
            producer.close()
            # Let the replication stream land the floor stamps on the
            # standbys before the read storm: follower serving is gated
            # on the floor, and an arm racing it would measure leader
            # fallbacks, not the plane.
            time.sleep(1.5)

            counts = [0] * consumers
            served = [0] * consumers
            fail: list[str] = []

            def member(ci: int) -> None:
                cc = ConsumerClient(
                    bootstrap, f"fan-{ci}",
                    transport=cluster.client(f"fan-{ci}"),
                    rpc_timeout_s=10.0, follower_reads=follower,
                )
                try:
                    empties = 0
                    while counts[ci] < total_msgs and empties < 200:
                        msgs = cc.consume("fanout", max_messages=16)
                        if msgs:
                            counts[ci] += len(msgs)
                            empties = 0
                        else:
                            empties += 1
                            time.sleep(0.01)
                    served[ci] = cc.follower_served
                except Exception as e:
                    fail.append(f"consumer {ci}: {type(e).__name__}: {e}")
                finally:
                    cc.close()

            threads = [
                _threading.Thread(target=member, args=(ci,), daemon=True)
                for ci in range(consumers)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            elapsed = time.perf_counter() - t0
            if fail or any(c != total_msgs for c in counts):
                raise AssertionError(
                    f"fan-out arm (consumers={consumers}, "
                    f"follower={follower}) not count-exact: wanted "
                    f"{total_msgs}/consumer, got {counts}; errors: {fail}"
                )
            return {
                "consumers": consumers,
                "follower_reads": follower,
                "msgs_per_sec": round(consumers * total_msgs / elapsed, 1),
                "elapsed_s": round(elapsed, 3),
                "follower_served": sum(served),
                "count_exact": True,
            }
        except BaseException:
            # ProcCluster pins its brokers to CPU and logs them (stderr
            # included) under tmp, which is about to go.
            _print_tails(_os.path.join(tmp, f"broker-{b.broker_id}.log")
                         for b in config.brokers)
            raise
        finally:
            cluster.stop()
            _shutil.rmtree(tmp, ignore_errors=True)

    arms = [one_arm(c, f) for c in consumer_counts for f in (False, True)]
    by_count = {}
    for c in consumer_counts:
        off = next(a for a in arms
                   if a["consumers"] == c and not a["follower_reads"])
        on = next(a for a in arms
                  if a["consumers"] == c and a["follower_reads"])
        by_count[str(c)] = round(
            on["msgs_per_sec"] / off["msgs_per_sec"], 2)
    return {
        "arms": arms,
        "msgs_per_consumer": total_msgs,
        "partitions": partitions,
        "speedup_on_vs_off": by_count,
        "host_cores": _os.cpu_count(),
    }


def _run_slo_convergence(target_ms: float = 25.0, light_s: float = 1.5,
                         heavy_s: float = 10.0) -> dict:
    """SLO autopilot time-to-SLO after a STEP-LOAD change (ISSUE 13):
    a 1-broker in-proc cluster runs with the control loop engaged, a
    light warm phase establishes the steady operating point, then the
    offered load steps to a saturating pipelined stream. The phase
    reads the controller's own tick history (admin.stats `slo`) and
    reports the wall-clock from the step to the first post-step window
    back inside the p99 target — plus whether the step ever breached
    it at all (on a fast host the static point may simply absorb the
    step; the number is a measurement, not an assertion — the
    contract lives in tests/test_slo_chaos.py)."""
    import time as _time

    from ripplemq_tpu.chaos.cluster import InProcCluster, make_cluster_config
    from ripplemq_tpu.client import ProducerClient
    from ripplemq_tpu.metadata.models import Topic

    config = make_cluster_config(
        1, topics=(Topic("slobench", 1, 1),),
        standby_count=0,
        slo_p99_ack_ms=target_ms, slo_tick_s=0.1,
        slo_chain_depth_max=4,
    )
    with InProcCluster(config) as cluster:
        cluster.wait_for_leaders()
        bootstrap = [b.address for b in config.brokers]
        producer = ProducerClient(
            bootstrap, transport=cluster.client("slobench-p"),
            rpc_timeout_s=10.0,
        )
        admin = cluster.client("slobench-admin")
        addr = config.brokers[0].address
        payload = b"s" * 16  # inside the small-engine payload_bytes
        try:
            t0 = _time.monotonic()
            while _time.monotonic() - t0 < light_s:
                producer.produce("slobench", payload, partition=0)
                _time.sleep(0.005)
            t_step = _time.time()
            waiters = []
            deadline = _time.monotonic() + heavy_s
            while _time.monotonic() < deadline:
                # Saturating pipelined step: a window of async batches
                # deep enough to queue the settle pipeline. Refusals
                # are EXPECTED here — the step exists to provoke the
                # breach, and once the shed machine engages this
                # quota-less producer draws `overloaded:` refusals the
                # async waiter surfaces as ProduceError; the phase
                # keeps offering load (that IS the measured scenario),
                # it must not die on the refusal it engineered.
                try:
                    while len(waiters) < 64:
                        waiters.append(producer.produce_batch_async(
                            "slobench", [payload] * 16, partition=0))
                    waiters.pop(0)()
                except Exception:
                    _time.sleep(0.005)
            for w in waiters:
                try:
                    w()
                except Exception:
                    pass
            st = admin.call(addr, {"type": "admin.stats"}, timeout=10.0)
        finally:
            producer.close()
        slo = st["slo"]
        hist = [row for row in slo["tick_history"] if row[0] >= t_step]
        breach_t = next((row[0] for row in hist if row[2] == 0.0), None)
        time_to_slo = None
        if breach_t is not None:
            rec_t = next((row[0] for row in hist
                          if row[0] > breach_t and row[2] == 1.0), None)
            if rec_t is not None:
                time_to_slo = round(rec_t - t_step, 3)
        return {
            "target_p99_ms": target_ms,
            "breached_after_step": breach_t is not None,
            "time_to_slo_s": time_to_slo,
            "adjustments": slo["adjustments"],
            "final_knobs": slo["knobs"],
            "final_p99_ms": slo["p99_ms"],
            "meeting_slo": slo["meeting_slo"],
        }


def _run_split_rebalance(warm_s: float = 1.5, tail_s: float = 1.5,
                         bucket_s: float = 0.25) -> dict:
    """Elastic-partition rebalance cost (ISSUE 17): a 3-broker in-proc
    cluster under sustained KEYED produce load splits its hottest
    partition online, and the phase reports the time-to-rebalance (the
    begin→cutover interval from the brokers' own flight recorders plus
    the wall-clock until every assignment is active again) and the
    throughput dip (worst ack-rate bucket touching the handoff window
    vs the pre-split average). Count-exact: every acked produce must be
    read back from the final logs — a lost write fails the phase, it
    does not average away."""
    import threading as _threading
    import time as _time

    from ripplemq_tpu.chaos.cluster import InProcCluster, make_cluster_config
    from ripplemq_tpu.chaos.harness import _drain_partition
    from ripplemq_tpu.client import ProducerClient
    from ripplemq_tpu.metadata.models import Topic

    topic = "splitbench"
    config = make_cluster_config(
        3, topics=(Topic(topic, 2, 3),), spare_slots=1,
        split_handoff_timeout_s=5.0,
    )
    with InProcCluster(config) as cluster:
        cluster.wait_for_leaders()
        bootstrap = [b.address for b in config.brokers]
        producer = ProducerClient(
            bootstrap, transport=cluster.client("splitbench-p"),
            metadata_refresh_s=0.2, rpc_timeout_s=5.0,
        )
        acks: list[float] = []          # ack wall-clock stamps
        stop = _threading.Event()

        def offered() -> None:
            i = 0
            while not stop.is_set():
                try:
                    producer.produce(topic, f"sb:{i}".encode(),
                                     key=f"k{i % 64:02d}".encode())
                except Exception:
                    continue  # refusals/reroutes retry as new payloads
                acks.append(_time.time())
                i += 1

        t = _threading.Thread(target=offered, daemon=True)
        t.start()
        try:
            _time.sleep(warm_s)
            t_split = _time.time()
            resp = cluster.admin_split(topic, 0)
            # Wall-clock until the routing table is fully active again.
            rebalanced_at = None
            deadline = _time.monotonic() + 30.0
            while _time.monotonic() < deadline:
                view = cluster.topic_view(topic)
                if view and all(a.state == "active" for a in view):
                    rebalanced_at = _time.time()
                    break
                _time.sleep(0.01)
            _time.sleep(tail_s)
        finally:
            stop.set()
            t.join(timeout=10)
            producer.close()
        n_acked = len(acks)
        # Count-exact readback over EVERY partition (child included).
        pids = sorted(a.partition_id for a in cluster.topic_view(topic))
        readback = sum(
            len(_drain_partition(cluster, topic, pid, tag=f"sb-{pid}"))
            for pid in pids
        )
        # Broker-side witnesses: begin→cutover interval + counters.
        admin = cluster.client("splitbench-a")
        cut_s = None
        forwarded = fences = 0
        for b in config.brokers:
            try:
                st = admin.call(b.address, {"type": "admin.stats"},
                                timeout=10.0)
                tr = admin.call(b.address, {"type": "admin.trace"},
                                timeout=10.0)
            except Exception:
                continue
            rc = st.get("reconfig") or {}
            forwarded += int(rc.get("forwarded_writes") or 0)
            fences += int(rc.get("fence_refusals") or 0)
            evs = {e["type"]: e["t"] for e in tr.get("trace", [])
                   if e.get("type") in ("split_begin", "split_cutover")}
            if "split_begin" in evs and "split_cutover" in evs:
                d = evs["split_cutover"] - evs["split_begin"]
                if d >= 0 and (cut_s is None or d < cut_s):
                    cut_s = round(d, 3)
        # Throughput: pre-split average vs the worst bucket in the
        # post-split window of the same length.
        pre = [a for a in acks if a < t_split]
        pre_rate = round(len(pre) / max(warm_s, 1e-6), 1)
        buckets: dict[int, int] = {}
        for a in acks:
            if a >= t_split:
                buckets[int((a - t_split) / bucket_s)] = (
                    buckets.get(int((a - t_split) / bucket_s), 0) + 1)
        n_buckets = max(1, int(tail_s / bucket_s))
        worst = min((buckets.get(i, 0) for i in range(n_buckets)),
                    default=0) / bucket_s
        if readback != n_acked:
            raise AssertionError(
                f"split_rebalance readback mismatch: acked {n_acked}, "
                f"read back {readback} (partitions {pids})"
            )
        return {
            "split_ok": bool(resp.get("ok")),
            "time_to_rebalance_s": (
                None if rebalanced_at is None
                else round(rebalanced_at - t_split, 3)),
            "begin_to_cutover_s": cut_s,
            "pre_split_acks_per_sec": pre_rate,
            "worst_post_split_bucket_acks_per_sec": round(worst, 1),
            "dip_ratio": (round(worst / pre_rate, 3) if pre_rate else None),
            "forwarded_writes": forwarded,
            "fence_refusals": fences,
            "acked": n_acked,
            "readback": readback,
        }


def _run_stripe_encode(mb: int = 4, reps: int = 3) -> float:
    """stripe_encode_mb_per_sec: GF(2⁸) RS(3,2) group-encode throughput
    at the sender's group-commit blob shape (one gf_matmul per blob —
    the Pallas kernel on TPU, the bit-linear XLA fallback elsewhere).
    Best-of-N over a fixed ~`mb` MB record batch; the first call pays
    the per-size-class compile and is excluded."""
    from ripplemq_tpu.stripes.codec import encode_group

    records = [(1, 0, i, bytes(64 << 10)) for i in range(mb * 16)]
    nbytes = sum(len(r[3]) for r in records)
    encode_group(records, 1, 0)  # compile the size class
    best = 0.0
    for r in range(1, reps + 1):
        t0 = time.perf_counter()
        encode_group(records, 1, r)
        dt = time.perf_counter() - t0
        best = max(best, nbytes / dt / 1e6)
    return round(best, 2)


def _run_repl_bytes(n_batches: int = 40, batch: int = 8,
                    payload_bytes: int = 400) -> dict:
    """Measured replication bytes per acked payload byte in BOTH
    replication modes, on a 5-broker in-proc cluster (controller + 4
    standbys — the R=5-equivalent durability shape the striping math
    targets: full-copy ships (R-1)=4 copies, striping (k+m)/k ≈ 1.67).

    Bytes are the modes' own acked-stream counters (`repl.bytes` /
    `stripes.bytes`: payload/frame bytes of standby-acked replication
    RPCs); acked bytes are counted client-side. Both numerators carry
    the same real overheads — slot padding to slot_bytes, REC_PIDSEQ /
    REC_OFFSETS records, stripe frame headers — so the ratio is the
    honest hot-path lever, not a geometry identity."""
    import tempfile
    import shutil

    from ripplemq_tpu.chaos.cluster import (
        InProcCluster,
        make_cluster_config,
        small_engine,
    )
    from ripplemq_tpu.client import ProducerClient
    from ripplemq_tpu.metadata.models import Topic

    out: dict = {}
    for mode in ("full", "striped"):
        tmp = tempfile.mkdtemp(prefix=f"replbytes-{mode}-")
        config = make_cluster_config(
            n_brokers=5, topics=(Topic("rb", 1, 3),),
            engine=small_engine(1, 3, slots=1024, slot_bytes=512,
                                max_batch=16),
            replication=mode, standby_count=4,
        )
        cluster = InProcCluster(config, data_dir=tmp)
        counters = {}
        try:
            cluster.start()
            cluster.wait_for_leaders()
            deadline = time.time() + 60
            ctrl = None
            while time.time() < deadline:
                st = cluster.client("rb").call(
                    cluster.broker_addr(0), {"type": "admin.stats"},
                    timeout=5.0,
                )
                if len(st["controller"]["standbys"]) >= 4:
                    ctrl = st["controller"]["id"]
                    break
                time.sleep(0.1)
            assert ctrl is not None, "standby set never reached 4"
            prod = ProducerClient(
                [b.address for b in config.brokers],
                transport=cluster.client("rb-prod"),
                metadata_refresh_s=0.5,
            )
            acked = 0
            for i in range(n_batches):
                prod.produce_batch(
                    "rb", [bytes([i & 0xFF]) * payload_bytes] * batch,
                    partition=0,
                )
                acked += batch * payload_bytes
            prod.close()
            # Let the in-flight tail (striped mode's remaining m
            # stripes stream past the k-ack settle) drain: poll the
            # counters until they stop moving.
            last = -1
            for _ in range(50):
                m = cluster.client("rb-m").call(
                    cluster.broker_addr(ctrl), {"type": "admin.metrics"},
                    timeout=5.0,
                )
                counters = m["metrics"]["counters"]
                total = (counters.get("repl.bytes", 0)
                         + counters.get("stripes.bytes", 0))
                if total == last:
                    break
                last = total
                time.sleep(0.2)
        finally:
            cluster.stop()
            shutil.rmtree(tmp, ignore_errors=True)
        repl_bytes = (counters.get("repl.bytes", 0)
                      + counters.get("stripes.bytes", 0))
        out[mode] = {
            "repl_bytes": int(repl_bytes),
            "acked_payload_bytes": int(acked),
            "per_acked_byte": round(repl_bytes / max(1, acked), 3),
            "stripe_groups": int(counters.get("stripes.groups", 0)),
        }
    out["striped_vs_full"] = round(
        out["striped"]["per_acked_byte"] / out["full"]["per_acked_byte"],
        3,
    )
    return out


def _run_codec(batch: int = 256, payload_bytes: int = 100,
               iters: int = 400) -> dict:
    """Codec throughput on the produce-frame shape (the host-path codec
    lever): encode+decode MB/s of a `batch`-message request through the
    bulk vector fast path vs the generic per-value recursion — both
    decode to the same value (wire/codec.py)."""
    import time as _time

    from ripplemq_tpu.wire import codec

    payloads = [
        (b"codec-%06d|" % i).ljust(payload_bytes, b"x") for i in range(batch)
    ]
    req = {"type": "produce", "topic": "bench", "partition": 0,
           "messages": payloads}
    out = {}
    for name, bulk in (("bulk", True), ("generic", False)):
        raw = codec.encode(req, bulk=bulk)
        mb = len(raw) / 1e6
        t0 = _time.perf_counter()
        for _ in range(iters):
            codec.encode(req, bulk=bulk)
        enc_s = (_time.perf_counter() - t0) / iters
        t0 = _time.perf_counter()
        for _ in range(iters):
            codec.decode(raw)
        dec_s = (_time.perf_counter() - t0) / iters
        out[f"encode_mb_per_sec_{name}"] = round(mb / enc_s, 1)
        out[f"decode_mb_per_sec_{name}"] = round(mb / dec_s, 1)
    # Headline: the bulk round trip (one encode + one decode per frame,
    # what each produce body pays on the wire).
    out["codec_mb_per_sec"] = round(
        2.0 / (1.0 / out["encode_mb_per_sec_bulk"]
               + 1.0 / out["decode_mb_per_sec_bulk"]), 1)
    return out


def _round_rtt(cfg, samples: int = 8) -> float:
    """Median single-round dispatch+fetch time (ms): the latency floor of
    one quorum round on this chip/link."""
    fns, alive, quorum, build = _make(cfg)
    inp = build(cfg, appends={0: [PAYLOAD]}, leader=0, term=1)
    state = fns.init()
    for _ in range(3):  # compile + warm
        state, out = fns.step(state, inp, alive, quorum)
    np.asarray(out.committed)
    ts = []
    for _ in range(samples):
        t0 = time.perf_counter()
        state, out = fns.step(state, inp, alive, quorum)
        np.asarray(out.committed)  # host fetch = execution fence
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e3)


# ---------------------------------------------------------------- gate
# Named headline metrics the `--compare BASELINE.json` regression gate
# watches, with the direction that counts as better. Everything else in
# the artifact is context (curves, A/B arms, configs) — the gate only
# trips on the numbers the README quotes.
HEADLINE_GATES = (
    ("value", "higher"),                       # engine sustained rate
    ("shipped_shape_appends_per_sec", "higher"),
    ("consume_msgs_per_sec", "higher"),
    ("codec_mb_per_sec", "higher"),
    ("stripe_encode_mb_per_sec", "higher"),
    ("e2e_appends_per_sec", "higher"),
    ("e2e_consume_msgs_per_sec", "higher"),
    ("p99_ack_ms", "lower"),
)
REGRESSION_PCT = 15.0


def _archive_result(result: dict) -> str:
    """Write the run's artifact next to the historical BENCH_r<NN>.json
    archives (next free number) so every run leaves a comparable
    baseline behind — the gate's denominators are never hand-curated."""
    import os
    import re

    root = os.path.dirname(os.path.abspath(__file__))
    taken = [
        int(m.group(1))
        for f in os.listdir(root)
        if (m := re.fullmatch(r"BENCH_r(\d+)\.json", f))
    ]
    path = os.path.join(root, "BENCH_r%02d.json" % (max(taken, default=0) + 1))
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    return path


def _load_baseline(path: str) -> dict:
    """A baseline is either a bare bench artifact (what _archive_result
    writes) or a driver wrapper holding one under `parsed`/`tail`."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "metric" in doc:
        return doc
    if isinstance(doc, dict):
        if isinstance(doc.get("parsed"), dict):
            return doc["parsed"]
        tail = doc.get("tail") or ""
        i = tail.find('{"metric"')
        if i >= 0:
            return json.loads(tail[i:])
        # Front-truncated tail (fixed-size stdout capture cut the
        # artifact's head off). The cut usually lands inside the first
        # string value, so re-opening the object with a dummy key
        # recovers every complete key after the cut point.
        try:
            rec = json.loads('{"_truncated": "' + tail)
        except ValueError:
            rec = None
        if isinstance(rec, dict) and any(
                k in rec for k, _ in HEADLINE_GATES):
            return rec
    raise SystemExit(f"--compare: no bench artifact found in {path}")


def compare_results(result: dict, baseline: dict,
                    threshold_pct: float = REGRESSION_PCT) -> list[str]:
    """Regression gate: every HEADLINE_GATES metric present in BOTH
    artifacts must not be worse than the baseline by > threshold_pct.
    Returns the failure lines (empty = gate passes); prints one verdict
    line per compared metric to stderr."""
    import sys

    failures: list[str] = []
    for key, direction in HEADLINE_GATES:
        if key not in result or key not in baseline:
            continue
        cur, base = float(result[key]), float(baseline[key])
        if base == 0:
            continue
        # Positive delta_pct = worse, in either direction's terms.
        delta = ((base - cur) if direction == "higher" else (cur - base)) \
            / abs(base) * 100.0
        worse = delta > threshold_pct
        print("compare: %-32s %14.3f -> %14.3f  %+7.2f%% %s"
              % (key, base, cur, -delta if direction == "higher" else delta,
                 "REGRESSED" if worse else "ok"), file=sys.stderr)
        if worse:
            failures.append(
                f"{key}: {base} -> {cur} "
                f"({delta:.1f}% worse, limit {threshold_pct}%)")
    return failures


def _operating_curve_main(out_path: str) -> None:
    """Standalone rails-prior phase: measure the (coalesce, chain_depth)
    operating curve at the headline latency shape and write an
    `slo_rails_file` JSON prior — the AIMD controller then starts from
    this machine's measured knee instead of the shipped rail defaults
    (slo/controller.py _load_rails).

    Rail derivation from the measured curve: among the light-load
    service points, the largest coalesce budget whose p99 stays within
    25% of the measured floor becomes the coalesce rail ceiling; the
    chain depth of the highest-throughput point (chained points
    included) becomes the depth ceiling. Floors stay at the latency-
    favoring end (0 s / depth 1)."""
    from ripplemq_tpu.core.config import EngineConfig

    lat_cfg = EngineConfig(
        partitions=1024, replicas=5, slots=2048, slot_bytes=128,
        max_batch=32, read_batch=32, max_consumers=64, max_offset_updates=8,
    )
    curve = _run_curve(lat_cfg)
    light = [pt for pt in curve if "window" not in pt]
    floor_p99 = min(pt["p99_ack_ms"] for pt in light)
    ok_budget = [pt for pt in light
                 if pt["p99_ack_ms"] <= 1.25 * floor_p99]
    best = max(curve, key=lambda pt: pt["appends_per_sec"])
    rails = {
        "read_coalesce_min_s": 0.0,
        "read_coalesce_max_s": max(pt["coalesce_s"] for pt in ok_budget),
        "chain_depth_min": 1,
        "chain_depth_max": int(best["chain_depth"]),
    }
    prior = {
        "method": "bench.py operating_curve",
        "floor_p99_ack_ms": floor_p99,
        "rails": rails,
        "curve": curve,
    }
    with open(out_path, "w") as f:
        json.dump(prior, f, indent=1)
        f.write("\n")
    print(json.dumps({"rails": rails, "floor_p99_ack_ms": floor_p99,
                      "out": out_path}))


def main(compare: "str | None" = None) -> None:
    import jax

    from ripplemq_tpu.core.config import EngineConfig
    from ripplemq_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    # Scale the ENGINE phases to the accelerator actually present: the
    # window sizes were tuned for a TPU (hundreds of millions of rows
    # per timed window); on a CPU-only host the same windows run for
    # hours and the artifact never lands. The sustained METHOD is
    # unchanged — only the window length shrinks (still hundreds of
    # launches, still ring-wrapping, still tail-verified).
    on_cpu = jax.default_backend() == "cpu"
    eng_launches = 48 if on_cpu else 480
    eng_windows = 2 if on_cpu else 3
    ab_launches = 32 if on_cpu else 240
    parity_launches = 32 if on_cpu else 240

    # TPU mode: 1k partitions, RF 5, full 256-row batches, 8-round chains
    # (B swept: rounds are DMA-issue-bound, so bytes-per-DMA is nearly
    # free throughput until ~B=256; B=512 regresses). The HEADLINE is
    # the steady-state rate (ring wraps behind the host-advanced trim,
    # exactly how the broker drives retention); the old burst-window
    # figure is kept as the cross-round comparability row. slots must
    # avoid a power-of-two partition stride: S x SB = 2^20 (e.g. slots
    # 8192 at SB 128) costs ~35% to HBM aliasing (PROFILE.md r5).
    tpu_cfg = EngineConfig(
        partitions=1024, replicas=5, slots=12352, slot_bytes=128,
        max_batch=256, read_batch=32, max_consumers=64, max_offset_updates=8,
    )
    tpu_rate = _run_sustained(tpu_cfg, chain=8, launches=eng_launches,
                              windows=eng_windows, verify=True)
    burst_rate = _run_mode(tpu_cfg, batch_per_partition=256, rounds=48,
                           warmup=1, verify=True, chain=8)

    # The SHIPPED example shape (examples/cluster.yaml engine:) at the
    # broker's default chain depth — the configuration users actually
    # boot, measured as shipped.
    shipped_cfg = EngineConfig(
        partitions=8, replicas=3, slots=4096, slot_bytes=256,
        max_batch=32, read_batch=32, max_consumers=64, max_offset_updates=8,
    )
    # 96 rounds x 32 rows = 3072 < 4096 slots (no store/trim here, so
    # the timed window must fit the ring).
    shipped_rate = _run_mode(shipped_cfg, batch_per_partition=32,
                             rounds=96, warmup=2, chain=4)

    # Baseline mode: the reference's shape — 1 partition, RF 5, ONE entry
    # per strictly-sequential round (max_batch stays at the ALIGN minimum;
    # only one row per round carries a payload). Measured with the SAME
    # sustained method as the numerator (ring wraps behind trim, window
    # long enough to amortize the fixed window cost) so vs_baseline
    # compares architectures, not measurement methods; rounds stay
    # semantically sequential — each depends on the previous state.
    base_cfg = EngineConfig(
        partitions=1, replicas=5, slots=2048, slot_bytes=128,
        max_batch=8, read_batch=32, max_consumers=64, max_offset_updates=8,
    )
    base_rate = _run_sustained(base_cfg, chain=1,
                               launches=500 if on_cpu else 2000,
                               windows=eng_windows,
                               verify=True, batch_per_partition=1,
                               partitions=1)

    # Latency through the full host batcher uses the broker's default
    # shape (32-row windows): produce-ack latency is about small-round
    # service, where a 128-row window would just inflate the per-round
    # input transfer.
    lat_cfg = EngineConfig(
        partitions=1024, replicas=5, slots=2048, slot_bytes=128,
        max_batch=32, read_batch=32, max_consumers=64, max_offset_updates=8,
    )
    lat = _run_latency(lat_cfg)
    rtt_ms = _round_rtt(lat_cfg)
    curve = _run_curve(lat_cfg)
    # read_batch 128: the host-mirror consume path serves up to
    # read_batch rows per call, so bigger windows amortize the per-call
    # (lock + decode dispatch) overhead — the consumer-side analogue of
    # producer batching.
    consume_cfg = EngineConfig(
        partitions=1024, replicas=5, slots=2048, slot_bytes=128,
        max_batch=32, read_batch=128, max_consumers=64, max_offset_updates=8,
    )
    consume_rate = _run_consume(consume_cfg, consumers=32, rows_per_part=128)
    spmd = _run_spmd_parity(launches=parity_launches)
    # Scale-out curve (always on the virtual CPU mesh — subprocesses
    # force their own device counts regardless of the parent backend).
    spmd_scaling = _run_spmd_scaling()
    # ISSUE 1 tentpole A/B: fused control + packed writes vs the legacy
    # path, same process, headline shape (also runnable standalone:
    # profiles/control_ab.py).
    fusion_ab = _run_fusion_ab(launches=ab_launches,
                               control_launches=ab_launches,
                               windows=2)
    codec_stats = _run_codec()
    # ISSUE 9: the striped replication plane's byte accounting (full vs
    # striped replication bytes per acked byte at the 4-standby shape)
    # and the GF(2⁸) group-encode throughput.
    repl_bytes = _run_repl_bytes()
    stripe_encode = _run_stripe_encode()
    # ISSUE 7: multi-group drain through the consumer-group coordinator
    # (count-exact per group, shared offsets, generation fencing live).
    group_consume = _run_group_consume()
    # ISSUE 13: SLO autopilot time-to-SLO after a step-load change.
    slo_convergence = _run_slo_convergence()
    # ISSUE 17: online split under sustained keyed load — time-to-
    # rebalance + throughput dip, count-exact readback.
    split_rebalance = _run_split_rebalance()
    # ISSUE 16: fan-out consume A/B — follower reads OFF vs ON over
    # subprocess brokers, consumer-count sweep, count-exact per arm.
    consume_fanout = _run_consume_fanout()
    # ISSUE 18: control-plane wave batching at volume — proposal
    # collapse, leader heartbeat RPC load before/after, convergence.
    control_plane_storm = _run_control_plane_storm()
    e2e = _run_e2e()
    # ISSUE 12: the multi-core host plane's same-host worker sweep
    # (workers 1/2/4, subprocess clients everywhere, count-exact).
    host_plane_scaling = _run_host_plane_scaling()

    result = {
                "metric": "committed_appends_per_sec",
                "value": round(tpu_rate, 1),
                "unit": "appends/s",
                "vs_baseline": round(tpu_rate / base_rate, 2),
                "baseline_appends_per_sec": round(base_rate, 1),
                "config": "P=1024 R=5 B=256 chain=8 sustained",
                "burst_window_appends_per_sec": round(burst_rate, 1),
                "burst_window_config": "P=1024 R=5 B=256 chain=8 (r3/r4 method)",
                "shipped_shape_appends_per_sec": round(shipped_rate, 1),
                "shipped_config": "P=8 R=3 B=32 SB=256 chain=4",
                "p50_ack_ms": round(lat["p50"], 3),
                "p99_ack_ms": round(lat["p99"], 3),
                "p999_ack_ms": round(lat["p999"], 3),
                "round_rtt_ms": round(rtt_ms, 3),
                "operating_curve": curve,
                "consume_msgs_per_sec": round(consume_rate, 1),
                "spmd_parity": spmd,
                "spmd_scaling": spmd_scaling,
                "control_fusion_ab": fusion_ab,
                "codec_mb_per_sec": codec_stats["codec_mb_per_sec"],
                "codec_ab": codec_stats,
                "repl_bytes_per_acked_byte": repl_bytes,
                "stripe_encode_mb_per_sec": stripe_encode,
                "readback": "verified",
                "host_plane_scaling": host_plane_scaling,
                "slo_convergence": slo_convergence,
                "split_rebalance": split_rebalance,
                "consume_fanout": consume_fanout,
                **control_plane_storm,
                **group_consume,
                **e2e,
    }
    print(json.dumps(result))
    import sys

    print(f"archived -> {_archive_result(result)}", file=sys.stderr)
    if compare:
        failures = compare_results(result, _load_baseline(compare))
        if failures:
            raise SystemExit(
                "bench regression gate FAILED:\n  " + "\n  ".join(failures))
        print("bench regression gate: ok", file=sys.stderr)


if __name__ == "__main__":
    import sys as _sys

    if len(_sys.argv) > 2 and _sys.argv[1] == "_e2e_client":
        # e2e loadgen subprocess (jax-free): see _e2e_client_main.
        _e2e_client_main(_sys.argv[2])
    elif len(_sys.argv) > 1 and _sys.argv[1] == "consume_fanout":
        # Standalone fan-out A/B (the brokers are subprocesses; this
        # process never touches jax) — runnable without the full bench:
        #     python bench.py consume_fanout
        print(json.dumps({"consume_fanout": _run_consume_fanout()}))
    elif len(_sys.argv) > 1 and _sys.argv[1] == "split_rebalance":
        # Standalone elastic-split rebalance phase:
        #     python bench.py split_rebalance
        print(json.dumps({"split_rebalance": _run_split_rebalance()}))
    elif len(_sys.argv) > 1 and _sys.argv[1] == "control_plane_storm":
        # Standalone control-plane volume sweep (in-proc brokers, no
        # engine work):
        #     python bench.py control_plane_storm
        print(json.dumps(_run_control_plane_storm()))
    elif len(_sys.argv) > 1 and _sys.argv[1] == "operating_curve":
        # Standalone rails-prior phase — writes an slo_rails_file JSON
        # (default slo_rails.json) from the measured operating curve:
        #     python bench.py operating_curve [OUT.json]
        _operating_curve_main(
            _sys.argv[2] if len(_sys.argv) > 2 else "slo_rails.json")
    elif len(_sys.argv) > 1 and _sys.argv[1] == "--compare":
        # Full run + regression gate against a prior artifact (exits
        # nonzero on a >15% regression of any HEADLINE_GATES metric):
        #     python bench.py --compare BENCH_r05.json
        if len(_sys.argv) < 3:
            raise SystemExit("usage: python bench.py --compare BASELINE.json")
        main(compare=_sys.argv[2])
    else:
        main()
