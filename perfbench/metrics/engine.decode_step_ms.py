"""Device time per execution of the engine's decode-step program, from the
trace's program events in the window (`programs.DECODE`)."""
from perfbench import programs


def read(r):
    if r.trace is None:
        return None
    secs, n = programs.one_program_s(r.trace, programs.DECODE)
    return secs / n * 1e3 if n else None
