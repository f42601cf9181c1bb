"""Same-process A/B harness for the control-fusion + packed-write levers.

PROFILE.md r5 finding 3: the sustained engine is pinned by two BALANCED
overlapped phases — control (~0.445 ms/round at the headline shape,
fusion-boundary overhead) and writes (~0.42 ms of bytes at the effective
rate). ISSUE 1 ships one lever for each (EngineConfig.fused_control,
EngineConfig.packed_writes); this script makes the claimed numbers
reproducible with one command, same-process, best-of-N:

- control-only rounds (offsets-only: they commit but skip the write
  kernel) price the control phase per round, legacy vs fused — the
  0.445 ms -> <=0.35 ms target lives here;
- full sustained rounds price the end-to-end effect, all four flag
  combinations;
- quarter-batch sustained rounds price the packed-write lever where it
  actually moves fewer bytes (a full round's extent IS the full window).

Run:
  python profiles/control_ab.py              # headline TPU shape
  python profiles/control_ab.py --preset cpu # small shape for CPU hosts
  python profiles/control_ab.py --launches 120 --windows 2

Prints one JSON line (the same dict bench.py embeds as
`control_fusion_ab`) plus a readable table. Numbers are only comparable
WITHIN one invocation (same process, same host conditions) — exactly
like every other same-process A/B in bench.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Runnable as `python profiles/control_ab.py`: the repo root (where
# `ripplemq_tpu` and `bench` live) is this file's parent directory.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PRESETS = {
    # The bench headline shape (one real chip).
    "tpu": dict(shape={}, chain=8, launches=240, control_launches=240,
                windows=2),
    # Small enough for a CPU host to finish in minutes; same structure.
    "cpu": dict(
        shape=dict(partitions=64, replicas=3, slots=1024, slot_bytes=128,
                   max_batch=32),
        chain=4, launches=48, control_launches=48, windows=2,
    ),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tpu")
    ap.add_argument("--chain", type=int, default=None)
    ap.add_argument("--launches", type=int, default=None)
    ap.add_argument("--control-launches", type=int, default=None)
    ap.add_argument("--windows", type=int, default=None)
    args = ap.parse_args()

    from bench import _run_fusion_ab

    kw = dict(PRESETS[args.preset])
    for name in ("chain", "launches", "windows"):
        if getattr(args, name) is not None:
            kw[name] = getattr(args, name)
    if args.control_launches is not None:
        kw["control_launches"] = args.control_launches

    out = _run_fusion_ab(**kw)
    print(json.dumps(out))

    rows = [(k, v) for k, v in out.items() if k != "config"]
    width = max(len(k) for k, _ in rows)
    print(f"\n{out['config']}", file=sys.stderr)
    for k, v in rows:
        print(f"  {k:<{width}}  {v}", file=sys.stderr)


if __name__ == "__main__":
    main()
