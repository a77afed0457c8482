"""The served programs, as the profiler trace names them.

The engine jits its decode step from a `functools.partial` of
`lm_decode_step`, which JAX names `jit__unknown`, as it names any jit of a
partial; a later name holding `decode_step` is matched too. Its prefill is
the jitted `_prefill_impl`. A metric of one of these programs takes its
time from the one program that matches, and refuses a trace in which
several do, rather than count another program's time as the step's.
"""
from __future__ import annotations

from typing import Tuple

DECODE = ("jit__unknown", "decode_step")
PREFILL = ("jit__prefill_impl", "prefill")


def _match(name: str, patterns) -> bool:
    return any(name == p or (not p.startswith("jit__") and p in name)
               for p in patterns)


def device_s(trace, patterns) -> Tuple[float, int]:
    """Device seconds and executions of the programs whose name is one of
    the `jit__` patterns or holds one of the others."""
    hits = [d for name, ev in trace.programs.items()
            if _match(name, patterns) for _, d in ev]
    return sum(hits) / 1e9, len(hits)


def one_program_s(trace, patterns) -> Tuple[float, int]:
    """As `device_s`, for exactly one program: a trace in which more than
    one distinct program matches is an error."""
    names = sorted(n for n in trace.programs if _match(n, patterns))
    if len(names) > 1:
        raise ValueError(f"several programs match {patterns}: {names}")
    return device_s(trace, patterns)
