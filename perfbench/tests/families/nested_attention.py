"""A benchmark family that only the tests use: a dense block whose
attention sizes sit in a nested "attention" section, flattened here for
the dense family's program config, weights, reference and counts. It
shows that a family the harness has never seen is taken from its own file
alone."""
from perfbench import harness

_dense = harness.family("dense")


def _flat(cfg):
    out = {k: v for k, v in cfg.items() if k != "attention"}
    out.update(cfg["attention"], family="dense")
    return out


def model_config(cfg):
    return _dense.model_config(_flat(cfg))


def make_params(key, cfg):
    return _dense.make_params(key, _flat(cfg))


def logits_at(params, tokens, at, *, cfg, quant=None):
    return _dense.logits_at(params, tokens, at, cfg=_flat(cfg), quant=quant)


def decode_step_bytes(cfg, contexts):
    return _dense.decode_step_bytes(_flat(cfg), contexts)


def decode_flops(cfg, contexts):
    return _dense.decode_flops(_flat(cfg), contexts)


def prefill_flops(cfg, n):
    return _dense.prefill_flops(_flat(cfg), n)
