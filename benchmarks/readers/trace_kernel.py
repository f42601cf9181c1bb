"""Device time of the events whose name matches, from the traced window.

args:
  line    "modules" (one event per launched program) or "ops"
  match   regular expression, searched in the event name
  stat    "mean_ms"      mean device time of one matching event
          "roofline_pct" least time the chip could take for the bytes the
                         matching kernel had to move, over the time it took
  bytes   (roofline_pct) name of a function in benchmarks/kernel_bytes.py
"""

from __future__ import annotations

import re

from benchmarks import kernel_bytes


def read(args: dict, run: dict):
    tr = run.get("trace") or {}
    rx = re.compile(args["match"])
    if args["line"] == "modules":
        hits = [(v["count"], v["seconds"]) for n, v in
                (tr.get("modules") or {}).items() if rx.search(n)]
    else:
        counts = tr.get("op_counts") or {}
        hits = [(counts.get(n, 0), t) for n, t in
                (tr.get("all_ops") or {}).items() if rx.search(n)]
    count = sum(c for c, _ in hits)
    seconds = sum(t for _, t in hits)
    if not count or seconds <= 0:
        return None
    if args["stat"] == "mean_ms":
        return seconds / count * 1e3
    moved = getattr(kernel_bytes, args["bytes"])(run)
    if moved is None:
        return None
    least = moved / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
