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

One run per seed carries all three; each number is compared on its own line
with its own limit (0, exact), and each must fail. Run by the builder on
the chip at the cell's own size, and by tests/test_run_broken.py on the CPU
at rehearsal size. Never part of a benchmark run.

    python benchmarks/control.py --workload ref-compose.sync --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import Run, RunFailed, failing

MUST_FAIL = ("replicas.scanned", "delivery.differ", ".missing")


def control_run(workload: str, seed: int, seconds: float,
                rehearse: bool = False) -> tuple[dict, list[str]]:
    """(result, names of the compared numbers that failed their limit)."""
    run = Run(workload, seed, seconds, False, rehearse=rehearse,
              cluster_overrides={"standby_count": 1},
              fault="flip_delivered,short_replica",
              t_start_ns=time.monotonic_ns())
    out = run.run()
    return out, list(failing(run.numbers))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out, failed = control_run(args.workload, seed, args.seconds,
                                      args.rehearse)
        except RunFailed as e:
            print(f"control seed {seed}: run failed outright: {e}")
            ok = False
            continue
        # The run's result line: it names what failed.
        if args.rehearse:
            print(f"rehearsal (no device metric, not a result): "
                  f"{json.dumps(out)}", file=sys.stderr)
        else:
            print(json.dumps(out))
        caught = all(any(m in f for f in failed) for m in MUST_FAIL)
        print(f"control seed {seed}: correct={out['correct']} failed "
              f"numbers {failed} -> "
              f"{'caught' if caught and not out['correct'] else 'MISSED'}")
        ok = ok and caught and not out["correct"]
    print("control:", "every broken run came out not correct" if ok
          else "A BROKEN RUN PASSED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
