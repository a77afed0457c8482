"""Mean over the window's tick calls of the time from the end of the
worker's `actor.handle` span to the start of the head's `head.held` span
for the same call: the worker holding a finished result until its next
poll frame carries it to the head."""
from perfbench import spans


def read(r):
    calls = spans.tick_calls(r)
    if calls is None:
        return None
    return spans.mean_ms(c["head.held"].start - c["actor.handle"].end
                         for c in calls)
