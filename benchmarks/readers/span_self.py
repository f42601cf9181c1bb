"""Self time of some span kinds, per sampled produce call, as a median.

A span's self time is its duration less the part of it that its child
spans cover. Children that ran in another process are on another clock:
their intervals are merged among themselves, per process, and only the
merged LENGTH is taken off - no two clocks are ever compared. (The
arithmetic of the program's obs/assemble.py, kept here so that no PR can
change it; that module computes coverage, not self time.)

args: {"kinds": ["client.produce", "client.rpc"], "scale": 0.001}
"""

from __future__ import annotations

from benchmarks import stats


def union_len(ivals) -> float:
    total, end = 0.0, None
    for a, b in sorted(ivals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def self_times(spans: list[dict]) -> dict[int, float]:
    """span id -> self time in microseconds."""
    by_id = {int(r["span"]): r for r in spans}
    kids: dict[int, list[dict]] = {}
    for r in by_id.values():
        if int(r["parent"]) in by_id:
            kids.setdefault(int(r["parent"]), []).append(r)
    out = {}
    for sid, r in by_id.items():
        per_proc: dict[str, list] = {}
        for k in kids.get(sid, ()):
            a = float(k["t0"]) * 1e6
            per_proc.setdefault(k["proc"], []).append((a, a + k["dur_us"]))
        covered = 0.0
        for proc, ivals in per_proc.items():
            if proc == r["proc"]:  # same clock: clip to the parent
                lo = float(r["t0"]) * 1e6
                hi = lo + r["dur_us"]
                ivals = [(max(a, lo), min(b, hi)) for a, b in ivals
                         if b > lo and a < hi]
            covered += union_len(ivals)
        out[sid] = max(0.0, r["dur_us"] - min(covered, r["dur_us"]))
    return out


def read(args: dict, run: dict):
    spans = run.get("spans") or []
    if not spans:
        return None
    selfs = self_times(spans)
    kinds = set(args["kinds"])
    roots = {int(r["trace"]) for r in spans if r["kind"] == "client.produce"}
    per_trace: dict[int, float] = {}
    for r in spans:
        if r["kind"] in kinds and int(r["trace"]) in roots:
            per_trace[int(r["trace"])] = per_trace.get(int(r["trace"]), 0.0) \
                + selfs[int(r["span"])]
    if not per_trace:
        return None
    return stats.median(per_trace.values()) * float(args.get("scale", 1.0))
