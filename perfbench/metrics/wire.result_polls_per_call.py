"""Mean over the window's tick calls of the actor_result polls the client
made (the `polls` count of the program's `wire.call` span)."""
from perfbench import spans


def read(r):
    calls = spans.tick_calls(r)
    if not calls:
        return None
    return sum(c["wire.call"].attrs["polls"] for c in calls) / len(calls)
