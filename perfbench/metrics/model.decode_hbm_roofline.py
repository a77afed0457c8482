"""Share of the HBM roofline reached by the decode step: the bytes a step
needs (every layer's weights, the output head, and the KV entries of the
live contexts only; `costs.decode_step_bytes`) over the chip's HBM
bandwidth, over the step's device time. A step that reads the whole
max_len buffer, or idles inside, reads lower."""
from perfbench import costs, programs


def read(r):
    if r.trace is None:
        return None
    secs, n = programs.one_program_s(r.trace, programs.DECODE)
    steps = r.probes.within(r.probes.decodes, r.lo, r.hi)
    if not n or not steps:
        return None
    need = sum(costs.decode_step_bytes(r.cell.cfg, c) for _, c in steps) \
        / len(steps)
    return 100.0 * need / r.peaks()["hbm_bytes_per_s"] / (secs / n)
