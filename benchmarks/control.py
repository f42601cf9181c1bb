"""The control of `correct`: runs that MUST come out as not correct.

This system states no numeric precision; its configuration states
guarantees. So the control breaks them, underneath a run that is otherwise
the cell's own (same cluster, same traffic, a short window):

  * fewer acknowledgements: the cluster runs with standby_count 1, so an
    ack stands for two copies where the configuration promises three
    (`replicas.scanned` must fail its limit);
  * an answer altered where it is produced: the first message a consumer
    receives in the window has one byte flipped (`delivery.differ`);
  * one replica short of an acked message: after the clean stop, the last
    replica's newest segment loses its tail (`replica<N>.missing`).

One run per seed carries all three (`--breaks guarantees`); each number is
compared on its own line with its own limit (0, exact), and each must fail.
A fourth break has a run of its own, because its number would hide behind
the flipped byte's (`--breaks drop`, for cells whose delivery is `whole`):

  * one subscription one message short: the first batch consumer process 0
    receives in the window loses its first message, so ONE subscription
    (of however many the cell has) lacks one message of one partition
    (`delivery.missing` must read exactly 1).

Run by the builder on the chip at the cell's own size, and by
tests/test_run_broken.py and tests/test_subscriptions.py on the CPU at
rehearsal size. Never part of a benchmark run.

    python benchmarks/control.py --workload ref-compose.sync --seeds 1,2,3
    python benchmarks/control.py --workload omb-100p-1kb.steady \
        --seeds 1,2,3 --breaks guarantees,drop
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import Run, RunFailed, failing

# break -> (cluster overrides, faults, the numbers that must fail: a name
# ends in the string, or the number reads exactly the value)
BREAKS = {
    "guarantees": ({"standby_count": 1}, "flip_delivered,short_replica",
                   ("replicas.scanned", "delivery.differ", ".missing")),
    "drop": (None, "drop_delivered", (("delivery.missing", 1),)),
}


def control_run(workload: str, seed: int, seconds: float,
                rehearse: bool = False,
                breaks: str = "guarantees") -> tuple[dict, list[str]]:
    """(result, names of the compared numbers that failed their limit)."""
    overrides, fault, _ = BREAKS[breaks]
    run = Run(workload, seed, seconds, False, rehearse=rehearse,
              cluster_overrides=overrides, fault=fault,
              t_start_ns=time.monotonic_ns())
    out = run.run()
    return out, list(failing(run.numbers))


def caught(breaks: str, out: dict, failed: list[str]) -> bool:
    return not out["correct"] and all(
        any(f.endswith(m) for f in failed) if isinstance(m, str)
        else out.get(m[0]) == m[1] for m in BREAKS[breaks][2])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--breaks", default="guarantees",
                    help="comma list of " + ", ".join(BREAKS))
    args = ap.parse_args()
    ok = True
    for seed, breaks in ((int(s), b) for s in args.seeds.split(",")
                         for b in args.breaks.split(",")):
        try:
            out, failed = control_run(args.workload, seed, args.seconds,
                                      args.rehearse, breaks)
        except RunFailed as e:
            print(f"control seed {seed} {breaks}: run failed outright: {e}")
            ok = False
            continue
        # The run's result line: it names what failed.
        if args.rehearse:
            print(f"rehearsal (no device metric, not a result): "
                  f"{json.dumps(out)}", file=sys.stderr)
        else:
            print(json.dumps(out))
        hit = caught(breaks, out, failed)
        print(f"control seed {seed} {breaks}: correct={out['correct']} "
              f"failed numbers { {f: out[f] for f in failed} } -> "
              f"{'caught' if hit else 'MISSED'}")
        ok = ok and hit
    print("control:", "every broken run came out not correct" if ok
          else "A BROKEN RUN PASSED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
