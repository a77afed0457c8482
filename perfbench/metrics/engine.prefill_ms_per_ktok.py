"""Device time of the engine's prefill programs (`programs.PREFILL`) in the
window, per 1,000 prompt tokens the engine sent to them in the window."""
from perfbench import programs


def read(r):
    if r.trace is None:
        return None
    secs, n = programs.one_program_s(r.trace, programs.PREFILL)
    toks = sum(k for _, k in r.probes.within(r.probes.prefills, r.lo, r.hi))
    return secs * 1e3 / (toks / 1e3) if n and toks else None
