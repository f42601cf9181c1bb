"""Bytes a kernel has to move, computed from shapes and counts - never read
from the program's own arithmetic."""

from __future__ import annotations

from benchmarks.readers._common import series_name, window_pair


def append_rows(run: dict):
    """The append kernel over the traced window: every message appended is
    one row of `slot_bytes`, read once from the staged round and written to
    each of the R replica rings: rows x slot_bytes x (R + 1) bytes at the
    least. Rows = the controller's produce.messages over the traced window
    (alignment padding the kernel also writes is not counted: it is not
    work the algorithm needs)."""
    pair = window_pair(run, "trace")
    if pair is None:
        return None
    (ta, a), (tb, b) = pair
    name = series_name("produce.messages", "_total")
    if name not in b:
        return None
    tr = run["trace"]
    rows = (b[name] - a.get(name, 0.0)) * (
        (tr["stop_ns"] - tr["start_ns"]) / (tb - ta))
    eng = run["config"]["cluster"]["engine"]
    return rows * eng["slot_bytes"] * (eng["replicas"] + 1)
