"""Share of the answering slots' tick time spent waiting on another
request's admission, in percent: over the program's `engine.admit` spans
that start in the window, the wall of each times its `held` (the slots in
mid-answer when it started, which get no token until it ends), over the
`engine.tick` spans that decoded and start in the window, the wall of each
times its `active` slots. None on a program whose admissions record no
`held`, or where no tick decoded."""
from perfbench import spans


def read(r):
    admits = spans.in_window(r, "engine.admit")
    ticks = spans.in_window(r, "engine.tick")
    if admits is None or ticks is None:
        return None
    if any("held" not in s.attrs for s in admits):
        return None
    busy = sum((s.end - s.start) * s.attrs.get("active", 0) for s in ticks)
    if busy <= 0:
        return None
    stalled = sum((s.end - s.start) * s.attrs["held"] for s in admits)
    return 100.0 * stalled / busy
