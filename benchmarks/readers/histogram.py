"""Mean of one of the controller's stage histograms over the measured
window: (sum at the end - sum at the start) / (count at the end - count at
the start), times `scale`. The registry's bins are powers of two, so a
quantile read from them moves in factors of two; the window mean is exact.

args: {"name": "settle.commit_wait_us", "scale": 0.001}
"""

from benchmarks.readers._common import series_name, window_pair


def read(args: dict, run: dict):
    pair = window_pair(run)
    if pair is None:
        return None
    (_, a), (_, b) = pair
    s, c = series_name(args["name"], "_sum"), series_name(args["name"], "_count")
    if s not in b or c not in b:
        return None
    dn = b[c] - a.get(c, 0.0)
    if dn <= 0:
        return None
    return (b[s] - a.get(s, 0.0)) / dn * float(args.get("scale", 1.0))
