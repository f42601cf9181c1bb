#!/usr/bin/env python
"""One-command chaos soak: seeded nemesis + safety checker, JSON verdict.

    python profiles/chaos_soak.py --seed 7
    python profiles/chaos_soak.py --seed 7 --phases 6 --phase-s 1.0
    python profiles/chaos_soak.py --sweep 10           # seeds 0..9
    python profiles/chaos_soak.py --replay trace.json  # re-apply a trace
    python profiles/chaos_soak.py --backend proc --seed 3
        # real broker subprocesses over TCP: SIGKILL + disk-fault
        # schedules (torn tail / bit flip / lost sealed segment)

Every run prints ONE JSON document: seed, the applied fault trace, its
sha256 digest (byte-for-byte reproducible from the seed — re-running
`--seed N` yields the identical digest), per-phase convergence, the
safety-invariant violations (empty = safe), and workload counts. A
failing soak is therefore a complete bug report: ship the JSON, replay
with `--seed N` (or `--replay trace.json` after editing the schedule
down to a minimal reproducer).

Runs on the CPU backend by default (JAX_PLATFORMS=cpu, 8 virtual
devices) — the chaos plane attacks host-side consensus, replication,
and retry machinery; device kernels are exercised but not the target.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sweep", type=int, default=0,
                    help="run seeds 0..N-1 instead of --seed")
    ap.add_argument("--phases", type=int, default=3)
    ap.add_argument("--phase-s", type=float, default=0.6)
    ap.add_argument("--ops-per-phase", type=int, default=2)
    ap.add_argument("--brokers", type=int, default=3)
    ap.add_argument("--partitions", type=int, default=2)
    ap.add_argument("--backend", choices=["inproc", "proc"],
                    default="inproc",
                    help="'proc' boots real broker subprocesses over TCP "
                         "and drives SIGKILL + disk-fault schedules "
                         "(torn tail / bit flip / lost sealed segment) "
                         "instead of in-proc network faults; identical "
                         "JSON verdict schema")
    ap.add_argument("--groups", type=int, default=0,
                    help="run a consumer-group workload of N members "
                         "and join the REBALANCE-STORM ops to the "
                         "nemesis pool (member_pause / member_churn / "
                         "stale_commit) on either backend; the checker "
                         "adds the group invariants (no same-generation "
                         "dual ownership, acked commits survive "
                         "rebalance, stale commits fenced, bounded "
                         "post-storm convergence)")
    ap.add_argument("--churn-storm", action="store_true",
                    help="join the churn_burst op to the nemesis pool "
                         "(needs --groups): several members leave+rejoin "
                         "simultaneously so the control plane's wave "
                         "batching forms wide multi-member OP_BATCH "
                         "proposals whose boundaries race the same "
                         "phase's controller crashes/SIGKILLs; the group "
                         "invariants must hold unconditionally over the "
                         "batched path on either backend")
    ap.add_argument("--replication", choices=["full", "striped"],
                    default="full",
                    help="'striped' runs the cluster with Reed–Solomon "
                         "striped replication (stripes/) and joins the "
                         "STRIPE-HOLDER ops to the nemesis pool "
                         "(stripe_kill / stripe_partition, sized to m); "
                         "the checker holds the run to the k-of-k+m "
                         "durability contract")
    ap.add_argument("--timeline", action="store_true",
                    help="attach the merged fault-vs-lifecycle timeline "
                         "(nemesis fault ops + every broker's flight-"
                         "recorder events, sorted by wall clock) even on "
                         "clean runs; violating runs always carry it")
    ap.add_argument("--witness", action="store_true",
                    help="enable the runtime lock witness for the run "
                         "(in-proc backend): the verdict gains a "
                         "lock_witness section, and a witnessed "
                         "acquisition cycle or an edge outside the "
                         "static lock graph's closure "
                         "(analysis/lock_graph.py) is a violation")
    ap.add_argument("--postmortems", action="store_true",
                    help="attach per-broker admin.postmortem bundles even "
                         "on clean runs; violating runs always carry them")
    ap.add_argument("--slo", action="store_true",
                    help="run the cluster with the SLO autopilot engaged "
                         "(slo/controller.py): the verdict gains an `slo` "
                         "section and the degradation contract — shed "
                         "engages under a sustained fault, safety holds "
                         "while shedding, recovery to SLO within "
                         "slo_recover_s of heal — is checked as "
                         "first-class violations")
    ap.add_argument("--follower-reads", action="store_true",
                    help="run the cluster with the follower-read plane "
                         "on (broker/follower.py) and the workload "
                         "consumer routing through it (backlogged reads "
                         "go to leased standbys, refusals fall back to "
                         "the leader); the verdict gains a `follower` "
                         "section, and a follower answering above its "
                         "replicated settled floor is a first-class "
                         "violation; works on both backends and both "
                         "replication modes")
    ap.add_argument("--splits", type=int, default=0,
                    help="provision N spare engine slots and run the "
                         "cluster ELASTIC: the nemesis pool gains online "
                         "split_partition/merge_partitions ops (raced "
                         "against crashes and controller failover), the "
                         "producer workload goes keyed through the "
                         "generation-fenced routing, and the verdict "
                         "gains a `reconfig` section whose bounded "
                         "time-to-rebalance invariants are first-class "
                         "violations; works on both backends")
    ap.add_argument("--replay", type=str, default=None,
                    help="JSON file holding a recorded trace (or a full "
                         "verdict) to re-apply instead of generating "
                         "from --seed")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the verdict JSON to this path")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    from ripplemq_tpu.chaos import run_chaos

    schedule = None
    if args.replay:
        with open(args.replay) as f:
            doc = json.load(f)
        trace = doc["trace"] if isinstance(doc, dict) else doc
        if isinstance(doc, dict) and "backend" in doc:
            # A recorded verdict names the substrate that produced it;
            # replaying a proc trace (SIGKILL + disk ops) on the in-proc
            # backend would silently change what is being reproduced.
            args.backend = doc["backend"]
        if isinstance(doc, dict) and doc.get("replication"):
            args.replication = doc["replication"]  # same rationale
        if isinstance(doc, dict) and doc.get("splits"):
            # Elastic traces carry split/merge ops whose candidate
            # resolution needs the spare slots the recording ran with.
            args.splits = int(doc["splits"])
        n_phases = 1 + max((t.get("phase", 0) for t in trace), default=0)
        schedule = [[] for _ in range(n_phases)]
        for t in trace:
            op = {k: v for k, v in t.items() if k != "phase"}
            # restarts/heals are emitted by the nemesis itself.
            if op.get("op") not in ("restart", "restart_holder", "heal"):
                schedule[t.get("phase", 0)].append(op)

    seeds = list(range(args.sweep)) if args.sweep else [args.seed]
    results = []
    for seed in seeds:
        v = run_chaos(
            seed=seed,
            n_brokers=args.brokers,
            partitions=args.partitions,
            phases=args.phases,
            phase_s=args.phase_s,
            ops_per_phase=args.ops_per_phase,
            schedule=schedule,
            backend=args.backend,
            groups=args.groups,
            churn_storm=args.churn_storm,
            replication_mode=args.replication,
            include_timeline=args.timeline,
            include_postmortems=args.postmortems,
            lock_witness=args.witness,
            slo=args.slo,
            follower_reads=args.follower_reads,
            splits=args.splits,
            # Process boots (JAX import + XLA compiles per broker) put
            # convergence probes on a different clock than in-proc runs.
            converge_timeout_s=120.0 if args.backend == "proc" else 30.0,
        )
        results.append(v)
    out = results[0] if len(results) == 1 else {
        "sweep": len(results),
        "safe": all(r["safe"] for r in results),
        "unsafe_seeds": [r["seed"] for r in results if not r["safe"]],
        "runs": results,
    }
    doc = json.dumps(out, indent=1)
    print(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc)
    return 0 if (out["safe"] if "safe" in out else True) else 1


if __name__ == "__main__":
    sys.exit(main())
