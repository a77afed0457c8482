"""The dense family gives both configurations exactly what the benchmark
read before `families/dense.py` took over the weights, the reference and
the counts. `testdata/dense_pinned.json` holds those numbers, recorded
from the code before the move: each configuration file cut to a CPU size,
its weights drawn from a fixed seed, the reference's logits (float32 and
the fp8 control) at fixed tokens and positions, and the three counts at
fixed contexts of the configuration as it is run."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, weights
from perfbench.harness import BENCH, ROOT

dense = harness.family("dense")
PIN = json.loads((BENCH / "testdata" / "dense_pinned.json").read_text())
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
DENSE = [e for e in SPEC["configs"]
         if json.loads((ROOT / e["file"]).read_text())["family"] == "dense"]
# Thread counts change the CPU's float32 sums in the last place; fp8 then
# and again rounds an activation across a step (seen: 6e-3 at one logit,
# 5e-4 on the mean, one core against several). Dropping the fp8 rounding
# moves the mean by 0.02, a wrong width or order by the logits' std, 0.16.
F32_ATOL = 2e-5
FP8_MEAN_ATOL, FP8_ATOL = 2e-3, 0.03


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def cut(cfg):
    """A CPU size: 2 layers, 4 heads of 16, grouped (2 KV heads) where the
    configuration groups, full multi-head where it does not."""
    kv = 4 if cfg["n_kv_heads"] == cfg["n_heads"] else 2
    return dict(cfg, n_layers=2, d_model=64, n_heads=4, n_kv_heads=kv,
                d_ff=160, vocab_size=500, head_dim=16)


def params(cfg):
    key = jax.random.key(weights.key32(PIN["seed"]), impl="rbg")
    return jax.jit(lambda k: dense.make_params(k, cfg))(key)


@pytest.mark.parametrize("entry", DENSE, ids=lambda e: e["name"])
def test_heads_fill_d_model(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["head_dim"] * cfg["n_heads"] == cfg["d_model"]


@pytest.mark.parametrize("name", sorted(PIN["configs"]))
def test_weights_are_the_pinned_draw(name):
    c = cut(config(name))
    assert c["n_kv_heads"] == PIN["configs"][name]["n_kv_heads"]
    h = hashlib.sha256()
    for a in jax.tree.leaves(params(c)):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == PIN["configs"][name]["weights_sha256"]


@pytest.mark.parametrize("name", sorted(PIN["configs"]))
def test_reference_and_control_logits_are_the_pinned_ones(name):
    c = cut(config(name))
    p = params(c)
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, c["vocab_size"], 1024), jnp.int32)
    at = jnp.asarray(PIN["positions"], jnp.int32)
    pinned = PIN["configs"][name]["logits"]
    f32 = np.asarray(dense.logits_at(p, tokens, at, cfg=c))[:, PIN["ids"]]
    np.testing.assert_allclose(f32, pinned["float32"], rtol=0, atol=F32_ATOL)
    fp8 = np.asarray(dense.logits_at(p, tokens, at, cfg=c, quant="fp8")
                     )[:, PIN["ids"]]
    gap = np.abs(fp8 - np.asarray(pinned["fp8"]))
    assert gap.mean() <= FP8_MEAN_ATOL and gap.max() <= FP8_ATOL


@pytest.mark.parametrize("name", sorted(PIN["configs"]))
def test_counts_are_the_pinned_ones(name):
    c, pin, ctx = config(name), PIN["configs"][name], PIN["contexts"]
    assert dense.decode_step_bytes(c, ctx) == pin["decode_step_bytes"]
    assert dense.decode_flops(c, ctx) == pin["decode_flops"]
    assert dense.prefill_flops(c, PIN["prompt"]) == pin["prefill_flops"]
