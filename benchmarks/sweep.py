"""The builder's knee sweep: one boot of an open-loop cell, the offered rate
stepped through --rates, one `sweep step` line per rate (ack and delivery
latency, and whether they grow from the step's first third to its last).
Not part of a benchmark run; the rate it finds is written into the cell's
file by hand, with the sweep recorded in PERF.md.

    python benchmarks/sweep.py --workload omb-1024p-100b.steady \
        --rates 40000,60000,80000 --step-seconds 8 --seed 1
"""

from __future__ import annotations

import argparse
import json
import sys

from run import Run, RunFailed


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    run = Run(args.workload, args.seed, args.step_seconds * len(rates), False,
              rehearse=args.rehearse)
    run.cell["producers"]["params"]["rate_steps_msgs_per_s"] = rates
    try:
        out = run.run()
    except RunFailed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print("sweep result (not a benchmark result): " + json.dumps(out),
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
