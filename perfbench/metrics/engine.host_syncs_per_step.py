"""Device-to-host reads per decode step: the `syncs` counted by the engine's
read helper in the program's `engine.tick` spans that decoded and start in
the window, over their number (admissions inside a tick count in it)."""
from perfbench import spans


def read(r):
    got = spans.in_window(r, "engine.tick")
    if got is None:
        return None
    steps = [s for s in got if s.attrs.get("active")]
    if not steps:
        return None
    return sum(s.attrs["syncs"] for s in steps) / len(steps)
