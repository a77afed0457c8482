"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the per-layer
readers and `breakdown` use.

- The window is the host span `bench.window`, which the harness opens when
  it starts sending and closes when it stops.
- A device is a plane named `/device:TPU:<n>`. Its busy time is the union
  of the intervals of its `XLA Ops` events (its `XLA Modules` events where
  a trace has no op line), clipped to the window; busy_s is the mean over
  the devices used.
- Programs are the `XLA Modules` events, named by the jitted function with
  the trailing `(<id>)` dropped, so a name survives a recompile.
- Each idle gap of a device is named by the most specific host span open
  at its midpoint (`SPAN_ORDER`), or `host.other` where none is.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
# most specific first: what the host was doing while the device idled
SPAN_ORDER = ("engine.prefill", "engine.decode", "engine.tick",
              "wire.call", "router.submit", "router.tick", "client.idle")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[int, int]       # [start_ns, end_ns)


@dataclass
class Trace:
    window: Interval
    busy_ns: Dict[int, int]                        # device -> busy in window
    programs: Dict[str, List[Tuple[int, int]]]     # name -> (start, dur) ns
    idle_by_span: Dict[str, int] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name.strip()).strip()


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def covers(merged: List[Interval], t: int) -> bool:
    """Whether t lies in one of `merged` (sorted, disjoint)."""
    i = bisect.bisect_right(merged, (t, float("inf"))) - 1
    return i >= 0 and merged[i][0] <= t < merged[i][1]


def name_gap(gap: Interval, spans: Dict[str, List[Interval]]) -> str:
    """`spans`: name -> merged intervals."""
    mid = (gap[0] + gap[1]) // 2
    for name in SPAN_ORDER:
        if covers(spans.get(name, []), mid):
            return name
    return "host.other"


def read(path: Path) -> Optional[Trace]:
    """The reduced trace, or None where it holds no window span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans: Dict[str, List[Interval]] = {}
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Tuple[str, int, int]]] = {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if m is None:
                    if ev.name == WINDOW_SPAN or ev.name in SPAN_ORDER:
                        spans.setdefault(ev.name, []).append((s, s + d))
                elif line.name == "XLA Ops":
                    ops.setdefault(int(m.group(1)), []).append((s, s + d))
                elif line.name == "XLA Modules":
                    modules.setdefault(int(m.group(1)), []).append(
                        (program_name(ev.name), s, d))
    if WINDOW_SPAN not in spans:
        return None
    window = max(spans[WINDOW_SPAN], key=lambda iv: iv[1] - iv[0])
    programs: Dict[str, List[Tuple[int, int]]] = {}
    busy_ns: Dict[int, int] = {}
    idle: Dict[str, int] = {}
    merged = {n: merge(ivs) for n, ivs in spans.items()}
    for dev in sorted(set(ops) | set(modules)):
        for name, s, d in modules.get(dev, ()):
            if window[0] <= s < window[1]:
                programs.setdefault(name, []).append((s, d))
        ivs = ops.get(dev) or [(s, s + d) for _, s, d in modules.get(dev, ())]
        busy = merge(clip(ivs, window))
        busy_ns[dev] = sum(e - s for s, e in busy)
        for g in gaps(busy, window):
            name = name_gap(g, merged)
            idle[name] = idle.get(name, 0) + (g[1] - g[0])
    n_dev = max(1, len(busy_ns))
    return Trace(window=window, busy_ns=busy_ns, programs=programs,
                 idle_by_span={k: v // n_dev for k, v in idle.items()})


def breakdown(tr: Trace, top: int = 10) -> Dict[str, list]:
    """Programs by device seconds, and idle seconds by the host span open
    during them (mean over devices), each the `top` largest."""
    progs = sorted(((n, sum(d for _, d in ev) / 1e9)
                    for n, ev in tr.programs.items()), key=lambda x: -x[1])
    idle = sorted(((n, ns / 1e9) for n, ns in tr.idle_by_span.items()),
                  key=lambda x: -x[1])
    return {"device_ops": [list(p) for p in progs[:top]],
            "idle_gaps": [list(g) for g in idle[:top]]}
