"""From a profiler trace to the few numbers the benchmark reports.

`extract` reads an .xplane.pb with nothing but JAX (`ProfileData`) into a
plain dict - plane name -> line name -> [(event name, start_ns, dur_ns)] -
and `summarize` reduces that dict, so the reduction can be checked on a
small recorded dict (tests/) and every PR computes the same numbers the
same way:

  busy_s      union of the intervals in which an operation ran on a device
              (line "XLA Ops" of a "/device:TPU:n" plane), averaged over
              the devices that ran anything
  window_s    length of the traced window (host clock around it)
  device_ops  the ten operations with the most device time
  modules     per XLA module (one launched program): count and seconds
  idle_gaps   the ten longest gaps between device operations, each named
              by the host-side runtime event that overlaps it most
"""

from __future__ import annotations

import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    hits = []
    for root, _, files in os.walk(log_dir):
        hits += [os.path.join(root, f) for f in files
                 if f.endswith(".xplane.pb")]
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(hits, key=os.path.getmtime)


def extract(path: str, keep_host_events: int = 200_000) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        lines: dict = {}
        kept = 0
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for ev in line.events:
                if not is_dev:
                    if kept >= keep_host_events:
                        break
                    if ev.duration_ns < 20_000:
                        continue  # host noise: cannot name a long gap
                    kept += 1
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.duration_ns)))
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            planes[plane.name] = lines
    return planes


def _union(intervals):
    """Sorted, merged [(start, end)] of a list of (start, end)."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def summarize(planes: dict, window_s: float, top: int = 10) -> dict:
    busy = []
    op_time: dict[str, float] = {}
    op_count: dict[str, int] = {}
    modules: dict[str, list] = {}
    gaps = []
    host = [(n, s, s + d) for pname, lines in planes.items()
            if not pname.startswith(DEVICE_PREFIX)
            for evs in lines.values() for n, s, d in evs]
    host.sort(key=lambda e: e[1])
    for pname, lines in sorted(planes.items()):
        if not pname.startswith(DEVICE_PREFIX):
            continue
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        merged = _union([(s, s + d) for _, s, d in ops])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for n, _, d in ops:
            op_time[n] = op_time.get(n, 0.0) + d / 1e9
            op_count[n] = op_count.get(n, 0) + 1
        for n, _, d in lines.get(MODULES_LINE, []):
            m = modules.setdefault(n, [0, 0.0])
            m[0] += 1
            m[1] += d / 1e9
        gaps += [(b0, a1) for (_, b0), (a1, _) in zip(merged, merged[1:])]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        best, best_ov = "no host runtime event open", 0.0
        for n, s, e in host:
            if s >= b:
                break
            ov = min(e, b) - max(s, a)
            if ov > best_ov:
                best, best_ov = n, ov
        named.append([best[:80], (b - a) / 1e9])
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices_traced": len(busy),
        "window_s": float(window_s),
        "device_ops": [[n[:80], t] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "all_ops": op_time,
        "op_counts": op_count,
        "modules": {n: {"count": c, "seconds": t}
                    for n, (c, t) in modules.items()},
        "idle_gaps": named,
        "plane_lines": {p: sorted(ls) for p, ls in planes.items()},
    }

