"""Pallas TPU decode attention: one new token per slot against the stacked
KV cache, read in place.

Memory-bound by design. One grid step fetches one key block and one value
block for every head of one slot, so each cache byte is read once (the
R query heads of a KV head share its block); the online-softmax state
lives in VMEM scratch across a slot's blocks. The cache is the serving
engine's whole stacked buffer: the layer index is scalar-prefetched and
picks the layer in the blocks' index maps, so nothing outside the kernel
slices or copies a layer.

Only live blocks are fetched: a block index is clamped to the slot's live
rows, and a slot with none re-names the block the previous grid step
fetched, so the pipeline starts no DMA for either and `pl.when` skips
their compute. The step's own key and value are not in the cache yet: they
seed the online softmax before the first block (the caller writes them
after the layer loop).

Layout: q (B, Hq, D); k_new/v_new (B, Hkv, D); cache k/v (L, B, S, Hkv*D),
a token's heads side by side, so a head is a lane-aligned D-wide slice of
a block [bf16, or int8 with (L, B, Hkv, S) fp32 scales, a token per lane];
pos (B,) the rows
already cached, the new token's position. Out (B, Hq, D).
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
MAX_BLOCK_ROWS = 512
MAX_BLOCK_BYTES = 2 << 20    # K and V, double-buffered: 8 MB of scoped VMEM


def _block_rows(S: int, row_bytes: int) -> int:
    """Rows per key block: the largest power of two up to 512 whose block
    is at most 2 MB and divides S (S itself when S is shorter)."""
    fit = 1 << max(3, (MAX_BLOCK_BYTES // row_bytes).bit_length() - 1)
    bk = min(S, MAX_BLOCK_ROWS, fit)
    while S % bk:
        bk //= 2
    return bk


def _decode_kernel(layer_ref, start_ref, end_ref, src_ref, lo_ref, hi_ref,
                   q_ref, kn_ref, vn_ref, k_ref, v_ref, *rest, scale: float,
                   block_k: int, n_kb: int, n_kv: int, head_dim: int,
                   int8: bool):
    if int8:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    start, end = start_ref[b], end_ref[b]
    f32 = jnp.float32

    @pl.when(j == 0)
    def _seed():
        for h in range(n_kv):
            q = q_ref[h].astype(f32)                         # (R, D)
            s = jnp.sum(q * kn_ref[h].astype(f32), axis=1, keepdims=True)
            m_ref[h] = s * scale
            l_ref[h] = jnp.ones_like(l_ref[h])
            acc_ref[h] = jnp.broadcast_to(vn_ref[h].astype(f32),
                                          acc_ref[h].shape)

    @pl.when((j * block_k < end) & ((j + 1) * block_k > start))
    def _block():
        kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (1, block_k), 1)
        live = (kpos >= start) & (kpos < end)
        for h in range(n_kv):
            cols = slice(h * head_dim, (h + 1) * head_dim)
            k = k_ref[:, cols]                               # (bk, D)
            v = v_ref[:, cols].astype(f32)
            if int8:     # exact in bf16; the scales apply to s and p below
                k = k.astype(q_ref.dtype)
            s = jax.lax.dot_general(q_ref[h].astype(k.dtype), k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32) * scale
            if int8:
                s = s * ks_ref[h:h + 1, :]
            s = jnp.where(live, s, NEG_INF)                  # (R, bk)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            if int8:
                p = p * vs_ref[h:h + 1, :]
            # p stays f32: at the default precision the MXU rounds it to
            # bf16, which the f32 attention this replaces did not for MHA
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     precision=jax.lax.Precision.HIGHEST,
                                     preferred_element_type=f32)
            acc_ref[h] = alpha * acc_ref[h] + pv
            m_ref[h] = m_new

    @pl.when(j == n_kb - 1)
    def _finish():
        for h in range(n_kv):
            o_ref[h] = (acc_ref[h] / l_ref[h]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array, layer,
                     pos: jax.Array, *,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     window: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: bool = True) -> jax.Array:
    """Attention of q over layer `layer`'s cached rows [pos - window + 1,
    min(pos, S)) plus the step's own (k_new, v_new) at position pos.

    q (B,Hq,D); k_new/v_new (B,Hkv,D); k_cache/v_cache (L,B,S,Hkv*D);
    scales (L,B,Hkv,S); pos (B,) -> (B,Hq,D)."""
    B, Hq, D = q.shape
    Hkv = k_new.shape[1]
    R = Hq // Hkv
    S, HD = k_cache.shape[2], k_cache.shape[3]
    assert HD == Hkv * D, (k_cache.shape, k_new.shape)
    int8 = k_scale is not None
    bk = block_k or _block_rows(S, HD * k_cache.dtype.itemsize)
    assert S % bk == 0, (S, bk)
    n_kb = S // bk

    # the live rows of each slot, and the block range its DMAs cover
    pos = pos.reshape(B).astype(jnp.int32)
    end = jnp.minimum(pos, S)
    start = (jnp.maximum(pos - window + 1, 0) if window
             else jnp.zeros_like(pos))
    live = end > start
    lo, hi = start // bk, (end - 1) // bk
    # a slot with no live row fetches what the grid step before it did:
    # the last block of the nearest live slot before it (or block 0)
    src = jax.lax.cummax(jnp.where(live, jnp.arange(B), -1), axis=0)
    prev = jnp.where(src >= 0, hi[jnp.maximum(src, 0)], 0)
    src = jnp.maximum(src, 0)
    lo, hi = jnp.where(live, lo, prev), jnp.where(live, hi, prev)
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32), start, end, src,
               lo, hi)

    def cache_block(b, j, layer, start, end, src, lo, hi):
        return layer[0], src[b], jnp.clip(j, lo[b], hi[b]), 0

    def scale_block(b, j, layer, start, end, src, lo, hi):
        return layer[0], src[b], 0, jnp.clip(j, lo[b], hi[b])

    def per_slot(b, j, *_):
        return b, 0, 0, 0

    heads = lambda rows: pl.BlockSpec((None, Hkv, rows, D), per_slot)
    in_specs = [heads(R), heads(1), heads(1),
                pl.BlockSpec((None, None, bk, HD), cache_block),
                pl.BlockSpec((None, None, bk, HD), cache_block)]
    operands = [q.reshape(B, Hkv, R, D), k_new.reshape(B, Hkv, 1, D),
                v_new.reshape(B, Hkv, 1, D), k_cache, v_cache]
    if int8:
        in_specs += [pl.BlockSpec((None, None, Hkv, bk), scale_block)] * 2
        operands += [k_scale, v_scale]
    kernel = functools.partial(_decode_kernel, scale=1.0 / math.sqrt(D),
                               block_k=bk, n_kb=n_kb, n_kv=Hkv, head_dim=D,
                               int8=int8)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B, n_kb),
            in_specs=in_specs,
            out_specs=heads(R),
            scratch_shapes=[pltpu.VMEM((Hkv, R, D), jnp.float32),
                            pltpu.VMEM((Hkv, R, 1), jnp.float32),
                            pltpu.VMEM((Hkv, R, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*scalars, *operands)
    return out.reshape(B, Hq, D)
