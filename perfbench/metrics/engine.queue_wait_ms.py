"""Mean of the program's `engine.queue` spans that start in the window: a
request's wait in the engine's queue, from `add_request` to the admission
that pops it."""
from perfbench import spans


def read(r):
    got = spans.in_window(r, "engine.queue")
    if got is None:
        return None
    return spans.mean_ms(s.end - s.start for s in got)
