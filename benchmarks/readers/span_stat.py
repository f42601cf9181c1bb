"""A number from the span records of a span kind, of whichever process
recorded them: what lives on a broker the sampler does not ask (it reads
broker 0's `admin.metrics_text`; `admin.spans` is drained from every
broker). Only records that ENDED inside the measured window count. A
record's `t0` is its process's `time.perf_counter`, the window is the
harness's `time.monotonic_ns`: on Linux one clock (CLOCK_MONOTONIC), and
the processes of a run share a machine, as they must for the harness's
own delivery stamp.

A selection is {"kind": "follower.fetch"}, optionally with "min":
{"served": 1} (only records whose field is at least that) and "sum":
"served" or ["served", "refused"] (add the fields up instead of counting
the records).

args, one of:
  {"mean_us": <selection>, "less": <selection>, "scale": 0.001}
      the records' mean duration; with "less", the summed durations of
      that selection are taken off first (a request less its parks)
  {"ratio": {"num": <selection>, "den": <selection>}}

Nothing selected, or a denominator of 0: None.
"""

from __future__ import annotations


def select(sel: dict, run: dict) -> list[dict]:
    t0, t1 = run["t0_ns"], run["t1_ns"]
    out = []
    for r in run.get("spans") or ():
        if r.get("kind") != sel["kind"]:
            continue
        end_ns = float(r["t0"]) * 1e9 + float(r["dur_us"]) * 1e3
        if not t0 <= end_ns < t1:
            continue
        if any(float(r.get(k, 0) or 0) < v
               for k, v in sel.get("min", {}).items()):
            continue
        out.append(r)
    return out


def total(sel: dict, run: dict) -> float:
    recs = select(sel, run)
    fields = sel.get("sum")
    if fields is None:
        return float(len(recs))
    if isinstance(fields, str):
        fields = [fields]
    return float(sum(float(r.get(f, 0) or 0) for r in recs for f in fields))


def read(args: dict, run: dict):
    if "ratio" in args:
        den = total(args["ratio"]["den"], run)
        if not den:
            return None
        return total(args["ratio"]["num"], run) / den
    recs = select(args["mean_us"], run)
    if not recs:
        return None
    us = sum(float(r["dur_us"]) for r in recs)
    if "less" in args:
        us -= sum(float(r["dur_us"]) for r in select(args["less"], run))
    return max(0.0, us) / len(recs) * float(args.get("scale", 1.0))
