"""Pallas TPU decode attention: one new token against a long KV cache.

Memory-bound by design: the kernel streams the cache exactly once from HBM
(int8 cache halves the bytes; dequantization happens in VMEM), keeps the
online-softmax state in VMEM scratch, and applies the per-sequence validity
bound so continuous batching can mix sequences of different lengths.

Layout: q (B, Hq, D); k/v (B, Hkv, S, D) [bf16 or int8 + (B, Hkv, S, 1)
fp32 scales]; valid_len (B,) int32, scalar-prefetched into SMEM so the
kernel can branch on it. Out (B, Hq, D).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(vl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, block_k: int,
                   n_kb: int, int8: bool):
    b = pl.program_id(0)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = vl_ref[b]
    k_start = jk * block_k

    @pl.when(k_start < valid)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # (1, d) row block
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        if int8:
            k = k * ks_ref[0, 0]
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = kpos < valid
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None]) * mask
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_prev * alpha[:, None] + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(jk == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, *,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     block_k: int = 512, interpret: bool = True) -> jax.Array:
    """q (B,Hq,D); k/v (B,Hkv,S,D); valid_len (B,) -> (B,Hq,D)."""
    B, Hq, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    R = Hq // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    n_kb = S // block_k
    int8 = k.dtype == jnp.int8
    if k_scale is None:
        k_scale = jnp.ones((B, Hkv, S, 1), jnp.float32)
    if v_scale is None:
        v_scale = jnp.ones((B, Hkv, S, 1), jnp.float32)
    vl = valid_len.reshape(B).astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=1.0 / math.sqrt(D),
                               block_k=block_k, n_kb=n_kb, int8=int8)
    q3 = q.reshape(B, Hq, 1, D)
    # index maps take the scalar-prefetch ref after the grid indices
    kv_block = lambda b, h, j, vl: (b, h // R, j, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, j, vl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_block),
            pl.BlockSpec((1, 1, block_k, D), kv_block),
            pl.BlockSpec((1, 1, block_k, 1), kv_block),
            pl.BlockSpec((1, 1, block_k, 1), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, j, vl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), q.dtype),
        interpret=interpret,
    )(vl, q3, k, v, k_scale, v_scale)
    return out.reshape(B, Hq, D)
