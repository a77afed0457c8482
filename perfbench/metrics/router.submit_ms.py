"""Mean host wall of `Router.submit` in the window: placement plus one actor
round trip to hand the request to its replica."""


def read(r):
    subs = r.probes.within(r.probes.spans.get("router.submit", []), r.lo,
                           r.hi)
    if not subs:
        return None
    return sum(b - a for a, b in subs) / len(subs) * 1e3
