"""The program's own spans (`sf.*`, src/repro/spans.py) in a profiler
trace: per-span counts and times, and the split of each wave's forward
into dealer, online protocol and local share ops.

  load(path)                 the .xplane.pb -> [(start_ns, dur_ns, name)]
                             of every `sf.*` host event
  host_spans(spans, w0, w1)  name -> count, inclusive and self seconds
  wave_split(spans)          per `sf.wave.forward`: dealer, online and
                             local seconds, which sum to its duration
  wave_metrics(spans, w0, w1)  the per-wave means, in ms

It reads the same trace as bench/trace_reduce.py and uses its plain
event lists, so its numbers stand on the same clock as the device's.
"""
from __future__ import annotations

from bench import trace_reduce

PREFIX = "sf."
FORWARD = "sf.wave.forward"
DEALER = ("sf.mpc.dealer",)
ONLINE = ("sf.mpc.open", "sf.mpc.combine", "sf.mpc.trunc", "sf.mpc.cmp",
          "sf.mpc.share", "sf.mpc.reshare")
MPC_PREFIX = "sf.mpc."
KEY = "sf.mpc.key"


def load(path: str) -> list:
    """Every host event named `sf.*`, its name cut at any `#` (a
    TraceMe's inline metadata)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith(PREFIX):
                    out.append((e.start_ns, e.duration_ns, name))
    return out


def _length(merged) -> float:
    return sum(b - a for a, b in merged)


def _covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of `intervals` clipped to [lo, hi]."""
    return _length(trace_reduce._union(
        (max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi))


def _inside(spans, w0: float, w1: float):
    return [s for s in spans if s[0] >= w0 and s[0] + s[1] <= w1]


def host_spans(spans, w0: float, w1: float) -> dict:
    """For each span name inside [w0, w1]: its count, its inclusive
    seconds (the union of its intervals) and its self seconds (each
    interval less the spans nested directly inside it)."""
    spans = sorted(_inside(spans, w0, w1), key=lambda s: (s[0], -s[1]))
    out: dict[str, dict] = {}
    child = [0.0] * len(spans)
    stack: list[int] = []
    for i, (a, d, _) in enumerate(spans):
        while stack and spans[stack[-1]][0] + spans[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            child[stack[-1]] += d
        stack.append(i)
    by_name: dict[str, list] = {}
    for i, (a, d, name) in enumerate(spans):
        r = out.setdefault(name, {"count": 0, "self_s": 0.0})
        r["count"] += 1
        r["self_s"] += (d - child[i]) / 1e9
        by_name.setdefault(name, []).append((a, a + d))
    for name, iv in by_name.items():
        out[name]["incl_s"] = _length(trace_reduce._union(iv)) / 1e9
    return out


def wave_split(spans) -> list:
    """For every `sf.wave.forward`: (forward, dealer, online, local)
    seconds. dealer is the union of `sf.mpc.dealer` inside it; online
    the union of the online protocol's spans less what dealer covers;
    local the rest: local arithmetic, layout and key derivation
    (`sf.mpc.key` counts as local)."""
    mpc = [s for s in spans if s[2].startswith(MPC_PREFIX) and s[2] != KEY]
    out = []
    for f0, fd, name in spans:
        if name != FORWARD:
            continue
        f1 = f0 + fd
        inner = [(a, a + d, n) for a, d, n in mpc if a >= f0 and a + d <= f1]
        dealer = _covered([(a, b) for a, b, n in inner if n in DEALER], f0, f1)
        protocol = _covered([(a, b) for a, b, n in inner
                             if n in DEALER or n in ONLINE], f0, f1)
        every = _covered([(a, b) for a, b, _ in inner], f0, f1)
        out.append((fd / 1e9, dealer / 1e9, (protocol - dealer) / 1e9,
                    (fd - every) / 1e9))
    return out


def wave_metrics(spans, w0: float, w1: float) -> dict | None:
    """The per-wave means over the waves whose forward lies in [w0, w1],
    in ms: wave_input_ms, wave_wait_ms, dealer_ms, online_ms, local_ms,
    and forward_ms (their sum's reference). None without a wave."""
    spans = _inside(spans, w0, w1)
    split = wave_split(spans)
    if not split:
        return None
    n = len(split)

    def total(name):
        return sum(d for _, d, s in spans if s == name) / 1e9

    mean = [1000.0 * sum(col) / n for col in zip(*split)]
    return {"wave_input_ms": 1000.0 * total("sf.wave.input") / n,
            "wave_wait_ms": 1000.0 * total("sf.wave.wait") / n,
            "dealer_ms": mean[1], "online_ms": mean[2], "local_ms": mean[3],
            "forward_ms": mean[0]}
