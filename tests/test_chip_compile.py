"""Compile for a TPU v5e that is described, not attached: the Pallas
kernels at real widths with interpret=False, and the serving engine's
decode step for full-width llama3-8b cut to 2 layers. Interpret mode
cannot show what the chip's compiler refuses (block shapes off the
(8, 128) tiling, scalar operands outside SMEM); this can, with no chip.

The topology is described inside a fixture, never while modules are
imported: only one process may hold the TPU library, and a test file
that loaded it at import would break every other xdist worker."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ssm_scan import ssd_scan
from repro.models import build_model

LLAMA = get_config("llama3-8b")
MOE = get_config("phi3.5-moe-42b-a6.6b")
SSM = get_config("zamba2-2.7b")
T, SLOTS, MAX_LEN = 2048, 8, 1024


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
            lambda q, k, v, vl: decode_attention(q, k, v, vl,
                                                 interpret=False),
            (S((SLOTS, LLAMA.n_heads, hd)),
             S((SLOTS, LLAMA.cache_kv_heads, MAX_LEN, hd)),
             S((SLOTS, LLAMA.cache_kv_heads, MAX_LEN, hd)),
             S((SLOTS,), jnp.int32))),
        "decode_attention_int8": (
            lambda q, k, v, vl, ks, vs: decode_attention(
                q, k, v, vl, k_scale=ks, v_scale=vs, interpret=False),
            (S((SLOTS, LLAMA.n_heads, hd)),
             S((SLOTS, LLAMA.cache_kv_heads, MAX_LEN, hd), jnp.int8),
             S((SLOTS, LLAMA.cache_kv_heads, MAX_LEN, hd), jnp.int8),
             S((SLOTS,), jnp.int32),
             S((SLOTS, LLAMA.cache_kv_heads, MAX_LEN, 1), f32),
             S((SLOTS, LLAMA.cache_kv_heads, MAX_LEN, 1), f32))),
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


def test_engine_decode_step_compiles_for_v5e(one_chip):
    """The engine's decode program (cache donated) for llama3-8b at its
    published widths, 2 layers: weights, cache and step inputs on one
    chip, within its 16 GB."""
    cfg = LLAMA.replace(n_layers=2)
    model = build_model(cfg)
    on_chip = lambda tree: jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(SLOTS, MAX_LEN)))
    batch = {"tokens": _sds(one_chip, (SLOTS, 1), jnp.int32),
             "positions": _sds(one_chip, (SLOTS,), jnp.int32)}
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, batch).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes     # updated in place
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 16e9
