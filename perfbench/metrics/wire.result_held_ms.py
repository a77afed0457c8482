"""Mean over the window's tick calls of the program's `head.held` span: a
finished result held by the head until the client's actor_result poll
takes it."""
from perfbench import spans


def read(r):
    calls = spans.tick_calls(r)
    if calls is None:
        return None
    return spans.mean_ms(c["head.held"].end - c["head.held"].start
                         for c in calls)
