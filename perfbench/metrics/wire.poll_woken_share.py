"""Of the worker polls that handed over an actor_call in the window, the
share whose hold at the head an arrival ended (the program's
`head.poll_wait` span with `woke` 1, naming the calls its reply carried),
against polls that found the call already queued or whose hold ran out.
One poll reply hands its calls over at one instant, the end of their
`head.outbox` spans. None on a program that holds no poll."""
from perfbench import spans


def read(r):
    held = spans.held(r)
    if held is None:
        return None
    waits = [s for s in held if s.name == "head.poll_wait"]
    if not waits:
        return None
    woken = {c for s in waits if s.attrs.get("woke")
             for c in s.attrs.get("calls", ())}
    polls = {}
    for s in held:
        if s.name == "head.outbox" and r.lo <= s.start < r.hi:
            polls.setdefault(s.end, []).append(s.attrs.get("call"))
    if not polls:
        return None
    return sum(any(c in woken for c in calls)
               for calls in polls.values()) / len(polls)
