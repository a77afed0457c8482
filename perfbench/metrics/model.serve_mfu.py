"""The whole served step's share of the chips' peak: the flops the model
needs for the prompt tokens prefilled and the tokens decoded in the window
(`costs.prefill_flops`, `costs.decode_flops`), over window x chips x peak
bf16 FLOP/s."""
from perfbench import costs


def read(r):
    pre = r.probes.within(r.probes.prefills, r.lo, r.hi)
    dec = r.probes.within(r.probes.decodes, r.lo, r.hi)
    if r.trace is None or r.trace.busy_s <= 0 or not (pre or dec):
        return None
    flops = sum(costs.prefill_flops(r.cell.cfg, k) for _, k in pre) \
        + sum(costs.decode_flops(r.cell.cfg, c) for _, c in dec)
    return 100.0 * flops / ((r.hi - r.lo) * r.chips
                            * r.peaks()["bf16_flops_per_s"])
