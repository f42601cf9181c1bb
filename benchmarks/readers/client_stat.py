"""A tail of a series the load generator itself measured in the window:
"late_ms" (actual send - due time of each call) or "ack_ms" (send or due
time to ack, as the judged latency metrics take it), by the benchmark's
percentile rule. args: {"series": "late_ms", "pct": 99}"""

from benchmarks import stats


def read(args: dict, run: dict):
    xs = (run.get("client") or {}).get(args["series"])
    if xs is None or not len(xs):
        return None
    return stats.tail(xs, float(args.get("pct", 99)))[0]
