"""The program's own spans, as the per-layer readers take them.

The served path records its spans into `repro.core.metrics.SPANS`, a
fixed-size ring in the benchmark's own process; the readers read it after
the window. A reader takes the spans that start in the window `[r.lo,
r.hi)`, the rule of `Probes.within`, and returns None instead of a number
where there is nothing sound to read: an untraced run (`r.probes` is None),
a program that records no spans, or a ring that has dropped spans that may
have started in the window.
"""
from __future__ import annotations

from typing import Dict, List, Optional

# the parts of one actor call, each joined to its `wire.call` by `call` id
CALL_PARTS = ("head.outbox", "actor.handle", "head.held")


def held(r) -> Optional[list]:
    """Every span the ring holds, by start time, or None (see above)."""
    if r.probes is None:
        return None
    from repro.core import metrics
    ring = getattr(metrics, "SPANS", None)
    if ring is None or ring.horizon >= r.lo:
        return None
    return ring.spans()


def in_window(r, name: str) -> Optional[list]:
    """The spans called `name` that start in the window, or None."""
    spans = held(r)
    if spans is None:
        return None
    return [s for s in spans if s.name == name and r.lo <= s.start < r.hi]


def tick_calls(r) -> Optional[List[Dict[str, object]]]:
    """For each `tick` actor call whose `wire.call` starts in the window,
    that span and its `CALL_PARTS` by name; a call missing a part is left
    out. None where the spans cannot be read."""
    spans = held(r)
    if spans is None:
        return None
    parts = {(s.name, s.attrs.get("call")): s for s in spans
             if s.name in CALL_PARTS}
    out = []
    for s in spans:
        if (s.name == "wire.call" and s.attrs.get("kind") == "tick"
                and r.lo <= s.start < r.hi):
            got = {p: parts.get((p, s.attrs.get("call"))) for p in CALL_PARTS}
            if all(got.values()):
                out.append(dict(got, **{"wire.call": s}))
    return out


def mean_ms(values) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) * 1e3 if values else None
