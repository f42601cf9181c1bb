"""Ratio of two of the controller's series over the measured window, each
a counter (`_total`) or the sum of a histogram (`_sum`).

args: {"numerator": {"counter": "produce.messages"},
       "denominator": {"histogram_sum": "engine.chain_rounds"}}
"""

from benchmarks.readers._common import series_name, window_pair


def _delta(spec: dict, a: dict, b: dict):
    if "counter" in spec:
        name = series_name(spec["counter"], "_total")
    else:
        name = series_name(spec["histogram_sum"], "_sum")
    if name not in b:
        return None
    return b[name] - a.get(name, 0.0)


def read(args: dict, run: dict):
    pair = window_pair(run)
    if pair is None:
        return None
    (_, a), (_, b) = pair
    num, den = _delta(args["numerator"], a, b), _delta(args["denominator"], a, b)
    if num is None or not den:
        return None
    return num / den
