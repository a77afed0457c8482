"""Mean host wall of `Router.tick` in the window: one decode step of every
replica, each a synchronous actor round trip, and the harvest."""


def read(r):
    ticks = r.probes.within(r.probes.spans.get("router.tick", []), r.lo, r.hi)
    if not ticks:
        return None
    return sum(b - a for a, b in ticks) / len(ticks) * 1e3
