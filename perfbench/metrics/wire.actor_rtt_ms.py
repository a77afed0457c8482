"""Mean over the window's tick calls of the client-side wall of
`ActorReplicaHandle`'s call, less the worker-side wall of `ServeEngine.tick`
that served it: what the head's actor_call / actor_result protocol and the
worker's poll loop add to each decode step."""


def read(r):
    calls = r.probes.within(r.probes.tick_calls, r.lo, r.hi)
    ticks = r.probes.within(r.probes.spans.get("engine.tick", []), r.lo,
                            r.hi)
    if not calls or not ticks:
        return None
    call_s = sum(b - a for a, b in calls) / len(calls)
    tick_s = sum(b - a for a, b in ticks) / len(ticks)
    return (call_s - tick_s) * 1e3
