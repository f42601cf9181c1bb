"""Shared by the readers: the controller's metrics exposition as numbers,
and the snapshot nearest a moment."""

from __future__ import annotations


def parse_exposition(text: str) -> dict[str, float]:
    """`admin.metrics_text` -> {series name: value}, buckets left out."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#" or "{" in line:
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def series_name(metric: str, suffix: str) -> str:
    """The registry's dotted name as the exposition spells it."""
    return "ripplemq_" + "".join(
        ch if ch.isalnum() or ch == "_" else "_" for ch in metric) + suffix


def nearest(snapshots: list, t_ns: int):
    """The (t_ns, values) snapshot taken closest to t_ns; None if none."""
    if not snapshots:
        return None
    return min(snapshots, key=lambda s: abs(s[0] - t_ns))


def window_pair(run: dict, span: str = "window"):
    """The snapshots that bracket the measured window (or the traced
    window with span="trace"), or None."""
    if span == "trace":
        tr = run.get("trace") or {}
        if "start_ns" not in tr:
            return None
        a, b = tr["start_ns"], tr["stop_ns"]
    else:
        a, b = run["t0_ns"], run["t1_ns"]
    s0, s1 = nearest(run["snapshots"], a), nearest(run["snapshots"], b)
    if s0 is None or s1 is None or s1[0] <= s0[0]:
        return None
    return s0, s1
