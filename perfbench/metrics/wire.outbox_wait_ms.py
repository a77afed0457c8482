"""Mean over the window's tick calls of the program's `head.outbox` span:
from the head queueing the actor_call directive to the worker poll whose
reply hands it over."""
from perfbench import spans


def read(r):
    calls = spans.tick_calls(r)
    if calls is None:
        return None
    return spans.mean_ms(c["head.outbox"].end - c["head.outbox"].start
                         for c in calls)
