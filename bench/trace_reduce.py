"""From a profiler trace to device busy time, kernel time and idle gaps.

Two stages, so that the second can be checked on a small recorded
trace without the profiler:

  load(path)            the .xplane.pb -> plain lists: device op and
                        module intervals per device plane, and the
                        benchmark's own host spans ("bench.*")
  reduce(events, ...)   -> busy seconds (union of op intervals, mean
                        over devices), the window, time per module,
                        and the idle gaps attributed to the innermost
                        host span around each
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def latest_xplane(log_dir: str) -> str | None:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans, lines = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                lines.setdefault(plane.name, []).append(line.name)
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns, e.duration_ns, e.name)
                            for e in line.events]
            devices.append({"plane": plane.name, "ops": ops,
                            "modules": mods})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.duration_ns, e.name))
    return {"devices": devices, "spans": spans, "lines": lines}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def module_name(name: str) -> str:
    """'jit__combine(42)' -> 'jit__combine'; 'fusion.12' -> 'fusion'."""
    return _SUFFIX.sub("", name)


def reduce(events: dict, top: int = 10) -> dict | None:
    """Busy and idle time of the device inside the bench.window span.
    None where the trace holds no window or no device operation."""
    win = [s for s in events["spans"] if s[2] == WINDOW_SPAN]
    devs = [d for d in events["devices"] if d["ops"] or d["modules"]]
    if not win or not devs:
        return None
    w0 = win[0][0]
    w1 = w0 + win[0][1]
    busy, per_module, gaps_all = [], {}, []
    for d in devs:
        src = d["ops"] or d["modules"]
        iv = [(max(s, w0), min(s + du, w1)) for s, du, _ in src
              if s + du > w0 and s < w1]
        merged = _union(iv)
        busy.append(sum(b - a for a, b in merged))
        for s, du, name in d["modules"]:
            if s + du > w0 and s < w1:
                key = module_name(name)
                per_module[key] = per_module.get(key, 0.0) + du
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps_all.extend((edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i])
    spans = [s for s in events["spans"] if s[2] != WINDOW_SPAN]
    by_span: dict[str, float] = {}
    for a, b in gaps_all:
        mid = (a + b) / 2
        inner = [s for s in spans if s[0] <= mid <= s[0] + s[1]]
        name = (max(inner, key=lambda s: s[0])[2] if inner else "(no span)")
        by_span[name] = by_span.get(name, 0.0) + (b - a)
    n = len(devs)
    ops = sorted(per_module.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "n_devices": n,
        "module_s": {k: v / 1e9 / n for k, v in per_module.items()},
        "module_count": _count_modules(devs, w0, w1),
        "device_ops": [[k, v / 1e9 / n] for k, v in ops],
        "idle_gaps": [[k, v / 1e9 / n] for k, v in gaps],
    }


def _count_modules(devs, w0, w1) -> dict:
    out: dict[str, int] = {}
    for d in devs:
        for s, du, name in d["modules"]:
            if s + du > w0 and s < w1:
                key = module_name(name)
                out[key] = out.get(key, 0) + 1
    return {k: v / len(devs) for k, v in out.items()}
