"""Share of the decoding ticks that read a step launched ahead, in percent:
over the program's `engine.tick` spans that answered a slot (`active` > 0)
and start in the window, those whose `ahead` count is 1, because the step
they read was launched by the tick before and ran while the host returned
it and the next tick call came in. None on a program whose ticks count no
`ahead`."""
from perfbench import spans


def read(r):
    got = spans.in_window(r, "engine.tick")
    if got is None:
        return None
    steps = [s for s in got if s.attrs.get("active")]
    if not steps or any("ahead" not in s.attrs for s in steps):
        return None
    return 100.0 * sum(s.attrs["ahead"] for s in steps) / len(steps)
