"""Compile for a TPU v5e that is described, not attached: the Pallas
kernels at real widths with interpret=False, and the serving engine's
decode step for full-width llama3-8b cut to 2 layers and at the shapes of
the benchmark's cells. Interpret mode cannot show what the chip's
compiler refuses (block shapes off the (8, 128) tiling, scalar operands
outside SMEM); this can, with no chip.

The topology is described inside a fixture, never while modules are
imported: only one process may hold the TPU library, and a test file
that loaded it at import would break every other xdist worker."""
import dataclasses
import functools
import json
import os
import re
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ssm_scan import ssd_scan
from repro.models import build_model
from repro.serve.engine import ServeEngine

LLAMA = get_config("llama3-8b")
MOE = get_config("phi3.5-moe-42b-a6.6b")
SSM = get_config("zamba2-2.7b")
T, SLOTS, MAX_LEN = 2048, 8, 1024
CELL_CONFIGS = Path(__file__).resolve().parents[1] / "perfbench" / "configs"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_shapes(one_chip):
    S = lambda shape, dtype=jnp.bfloat16: _sds(one_chip, shape, dtype)
    hd = LLAMA.resolved_head_dim
    s = SSM.ssm
    H = s.expand * SSM.d_model // s.head_dim
    f32 = jnp.float32
    return {
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            (S((1, LLAMA.n_heads, T, hd)), S((1, LLAMA.n_kv_heads, T, hd)),
             S((1, LLAMA.n_kv_heads, T, hd)))),
        "decode_attention": (
            lambda q, kn, vn, k, v, layer, pos: decode_attention(
                q, kn, vn, k, v, layer, pos, interpret=False),
            (S((SLOTS, LLAMA.n_heads, hd)),
             S((SLOTS, LLAMA.cache_kv_heads, hd)),
             S((SLOTS, LLAMA.cache_kv_heads, hd)),
             S((2, SLOTS, MAX_LEN, LLAMA.cache_kv_heads * hd)),
             S((2, SLOTS, MAX_LEN, LLAMA.cache_kv_heads * hd)),
             S((), jnp.int32), S((SLOTS,), jnp.int32))),
        "decode_attention_int8": (
            lambda q, kn, vn, k, v, layer, pos, ks, vs: decode_attention(
                q, kn, vn, k, v, layer, pos, k_scale=ks, v_scale=vs,
                interpret=False),
            (S((SLOTS, LLAMA.n_heads, hd)),
             S((SLOTS, LLAMA.cache_kv_heads, hd)),
             S((SLOTS, LLAMA.cache_kv_heads, hd)),
             S((2, SLOTS, MAX_LEN, LLAMA.cache_kv_heads * hd), jnp.int8),
             S((2, SLOTS, MAX_LEN, LLAMA.cache_kv_heads * hd), jnp.int8),
             S((), jnp.int32), S((SLOTS,), jnp.int32),
             S((2, SLOTS, LLAMA.cache_kv_heads, MAX_LEN), f32),
             S((2, SLOTS, LLAMA.cache_kv_heads, MAX_LEN), f32))),
        "moe_gmm": (
            lambda x, w: moe_gmm(x, w, interpret=False),
            (S((MOE.moe.n_experts, 128, MOE.d_model)),
             S((MOE.moe.n_experts, MOE.d_model, MOE.d_ff)))),
        "ssd_scan": (
            lambda x, dt, A, Bm, Cm: ssd_scan(x, dt, A, Bm, Cm,
                                              chunk=s.chunk_size,
                                              interpret=False),
            (S((1, H, T, s.head_dim), f32), S((1, H, T), f32), S((H,), f32),
             S((1, s.n_groups, T, s.d_state), f32),
             S((1, s.n_groups, T, s.d_state), f32))),
    }


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "decode_attention_int8", "moe_gmm",
                                  "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_shapes(one_chip)[name]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_decode_step(one_chip, cfg, slots, max_len):
    """The engine's decode program (`ServeEngine._decode_step`: the
    model's step, its argmax and the advanced positions; cache donated),
    its kernels compiled for the chip, not interpreted."""
    model = build_model(cfg)
    on_chip = lambda tree: jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(slots, max_len)))
    batch = {"tokens": _sds(one_chip, (slots, 1), jnp.int32),
             "positions": _sds(one_chip, (slots,), jnp.int32),
             "live": _sds(one_chip, (slots,), jnp.int32)}
    step = functools.partial(ServeEngine._decode_step,
                             types.SimpleNamespace(model=model))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, batch).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    return compiled, cache_bytes


def test_engine_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The engine's decode program (cache donated) for llama3-8b at its
    published widths, 2 layers: weights, cache and step inputs on one
    chip, within its 16 GB."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    compiled, cache_bytes = _compile_decode_step(
        one_chip, LLAMA.replace(n_layers=2), SLOTS, MAX_LEN)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes     # updated in place
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 16e9


def test_int8_decode_step_writes_in_place(one_chip, monkeypatch):
    """qwen1.5-32b's int8 cache, 2 layers: the rows and their scales are
    written in place, and nothing lays a weight, the cache or its scales
    out anew."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    compiled, cache_bytes = _compile_decode_step(
        one_chip, get_config("qwen1.5-32b").replace(n_layers=2), SLOTS,
        MAX_LEN)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert max(_copy_bytes(text), default=0) < 1 << 20


@pytest.mark.parametrize("name,slots,max_len", [("granite-8b", 16, 2048),
                                                ("deepseek-llm-7b", 6, 4096)])
def test_decode_step_reads_the_cache_in_place(one_chip, monkeypatch, name,
                                              slots, max_len):
    """At a benchmark cell's shapes the decode step attends through the
    Pallas kernel into the stacked cache and writes one row per slot per
    layer: the cache stays aliased and no temporary reaches the size of
    one layer's K slice (a copy of a layer's cache would)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    raw = json.loads((CELL_CONFIGS / f"{name}.json").read_text())
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    cfg = ModelConfig(**{k: v for k, v in raw.items() if k in fields})
    compiled, cache_bytes = _compile_decode_step(one_chip, cfg, slots,
                                                 max_len)
    mem = compiled.memory_analysis()
    layer_k_slice = (slots * max_len * cfg.cache_kv_heads
                     * cfg.resolved_head_dim * 2)
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < layer_k_slice
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # nor does any copy move a weight or the cache into another layout
    assert max(_copy_bytes(text), default=0) < 1 << 20


_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "s8": 1, "u32": 4, "pred": 1}


def _copy_bytes(hlo_text):
    """Sizes of the arrays that `copy` instructions of the program make."""
    for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* copy(?:-start)?\(",
                         hlo_text):
        n = _BYTES.get(m.group(1), 4)
        for d in filter(None, m.group(2).split(",")):
            n *= int(d)
        yield n
