"""Share of the traced window in which no operation ran on the device:
100 x (1 - busy_s / window_s), from trace_reduce's summary. args: {}"""


def read(args: dict, run: dict):
    tr = run.get("trace") or {}
    if not tr.get("window_s") or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
