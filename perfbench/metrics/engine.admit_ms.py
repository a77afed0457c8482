"""Mean host wall of the program's `engine.admit` spans that start in the
window: one request's admission (prefill dispatch, first-token read, cache
scatter, slot writes)."""
from perfbench import spans


def read(r):
    got = spans.in_window(r, "engine.admit")
    if got is None:
        return None
    return spans.mean_ms(s.end - s.start for s in got)
