"""Shared model building blocks, pure JAX.

Attention here is pure jnp and blocked ("flash"), so its lowered memory is
linear in sequence length and the 512-device dry-run's memory_analysis
reflects a production-quality attention. Prefill and training take the
blockwise custom-VJP path (models/flash_vjp.py); the online-softmax loop
in `flash_attention_ref` serves the callers that bound each sequence's
keys (`valid_len`). The Pallas kernels in repro.kernels (kernels/ops.py)
are separate implementations of the same operations; the decode step of
the dense family (models/dense.py) calls the decode kernel.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.axes import constrain

F32 = jnp.float32
NEG_INF = -1e30


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, Dh), positions: (..., T) broadcastable."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[..., None, None] * freqs  # (..., T, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    h = jnp.einsum("btd,df->btf", x, w1)
    g = jnp.einsum("btd,df->btf", x, w3)
    h = jax.nn.silu(h.astype(F32)).astype(h.dtype) * g
    return jnp.einsum("btf,fd->btd", h, w2)


# ----------------------------------------------------------------------------
# Blocked flash attention (reference path; memory O(T * block))
# ----------------------------------------------------------------------------

def flash_attention_ref(
    q: jax.Array,                 # (B, Tq, Hq, Dh)
    k: jax.Array,                 # (B, Tk, Hkv, Dh)
    v: jax.Array,                 # (B, Tk, Hkv, Dh)
    *,
    causal: bool = True,
    q_offset: int = 0,            # absolute position of q[0] within the kv axis
    window: Optional[int] = None, # sliding-window size (None = full)
    block_q: int = 512,
    block_k: int = 512,
    valid_len: Optional[jax.Array] = None,  # (B,) traced per-seq kv validity bound
) -> jax.Array:
    """Blocked online-softmax attention with GQA folding.

    Without `valid_len` this is the blockwise custom-VJP attention
    (models/flash_vjp.py), whose backward saves only (q, k, v, o, lse)
    and recomputes p per tile. With it, the loop below runs: its outer
    loop over q-blocks is a static python loop so that each q-block
    scans only the kv-blocks its causal/window footprint needs -- the lowered
    FLOPs match a production flash kernel (no masked-out waste beyond block
    granularity).
    """
    B, Tq, Hq, Dh = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    R = Hq // Hkv
    scale = 1.0 / math.sqrt(Dh)

    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    nq = -(-Tq // block_q)
    nk = -(-Tk // block_k)
    assert Tq % block_q == 0 and Tk % block_k == 0, (Tq, Tk, block_q, block_k)

    if valid_len is None:
        from repro.models.flash_vjp import flash_attention_vjp
        return flash_attention_vjp(q, k, v, causal, window, q_offset,
                                   block_q, block_k)

    qr = q.reshape(B, nq, block_q, Hkv, R, Dh)
    kr = k.reshape(B, nk, block_k, Hkv, Dh)
    vr = v.reshape(B, nk, block_k, Hkv, Dh)

    out_blocks = []
    for i in range(nq):
        q_blk = qr[:, i]
        q_start = q_offset + i * block_q
        q_end = q_start + block_q - 1
        # kv-block footprint for this q block (static bounds)
        hi = nk if not causal else min(nk, (q_end // block_k) + 1)
        lo = 0
        if window is not None:
            lo = max(0, (q_start - window + 1) // block_k)
        n_steps = hi - lo
        if n_steps <= 0:
            out_blocks.append(jnp.zeros((B, block_q, Hkv, R, Dh), q.dtype))
            continue

        def body(carry, j):
            acc, m, l = carry
            kb = jax.lax.dynamic_index_in_dim(kr, j, axis=1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vr, j, axis=1, keepdims=False)
            s = jnp.einsum("bqhrd,bkhd->bhrqk", q_blk.astype(kb.dtype),
                           kb, preferred_element_type=F32) * scale
            qpos = q_start + jnp.arange(block_q)
            kpos = j * block_k + jnp.arange(block_k)
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            maskb = mask[None] & (kpos[None, None, :] < valid_len[:, None, None])
            s = jnp.where(maskb[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            # fully-masked rows keep p == 0 (avoid exp(-inf - -inf) == 1)
            p = jnp.exp(s - m_new[..., None]) * maskb[:, None, None]
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhrqk,bkhd->bhrqd", p, vb.astype(F32))
            acc_new = acc * alpha[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, Hkv, R, block_q, Dh), F32)
        m0 = jnp.full((B, Hkv, R, block_q), NEG_INF, F32)
        l0 = jnp.zeros((B, Hkv, R, block_q), F32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), lo + jnp.arange(n_steps))
        o = acc / jnp.maximum(l, 1e-20)[..., None]
        out_blocks.append(o.transpose(0, 3, 1, 2, 4).astype(q.dtype))  # (B,bq,Hkv,R,Dh)

    out = jnp.concatenate(out_blocks, axis=1) if len(out_blocks) > 1 else out_blocks[0]
    return out.reshape(B, Tq, Hq, Dh)


# ----------------------------------------------------------------------------
# Attention layer (GQA, rope, optional bias) with KV-cache support
# ----------------------------------------------------------------------------

def _q_head_permutation(n_heads, n_kv_heads, hq_pad, hkv_pad):
    """Padded q-head index of each real q head, preserving the GQA q->kv
    group mapping: real head i (group g=i//R, slot s=i%R) lands at
    g*R_pad + s, so under the padded ratio R_pad it still reads kv group g."""
    r_real = n_heads // n_kv_heads
    r_pad = hq_pad // hkv_pad
    return [(i // r_real) * r_pad + (i % r_real) for i in range(n_heads)]


def init_attention(key, d_model, n_heads, n_kv_heads, head_dim, qkv_bias, dtype,
                   pad_q_to: int = 0, pad_kv_to: int = 0):
    """Padded heads (pad_*_to > n_heads) get ZERO weights placed *within*
    their GQA group: a zero-weight q head yields zero output through zero wo
    rows, and real heads keep their kv group, so padding is numerically
    exact (DESIGN.md: TP-compat head padding, like vocab padding)."""
    ks = jax.random.split(key, 4)
    hq, hkv = pad_q_to or n_heads, pad_kv_to or n_kv_heads
    q_dim, kv_dim = hq * head_dim, hkv * head_dim
    std = d_model ** -0.5

    def expand_cols(w_real, perm, tot_heads):
        w = jnp.zeros((w_real.shape[0], tot_heads * head_dim), w_real.dtype)
        for i, j in enumerate(perm):
            w = w.at[:, j * head_dim:(j + 1) * head_dim].set(
                w_real[:, i * head_dim:(i + 1) * head_dim])
        return w

    wq_real = jax.random.normal(ks[0], (d_model, n_heads * head_dim)) * std
    wk_real = jax.random.normal(ks[1], (d_model, n_kv_heads * head_dim)) * std
    wv_real = jax.random.normal(ks[2], (d_model, n_kv_heads * head_dim)) * std
    wo_real = jax.random.normal(ks[3], (n_heads * head_dim, d_model)) * std

    if hq > n_heads or hkv > n_kv_heads:
        qperm = _q_head_permutation(n_heads, n_kv_heads, hq, hkv)
        kvperm = list(range(n_kv_heads))
        wq = expand_cols(wq_real, qperm, hq)
        wk = expand_cols(wk_real, kvperm, hkv)
        wv = expand_cols(wv_real, kvperm, hkv)
        wo = expand_cols(wo_real.T, qperm, hq).T
    else:
        wq, wk, wv, wo = wq_real, wk_real, wv_real, wo_real

    p = {"wq": wq.astype(dtype), "wk": wk.astype(dtype),
         "wv": wv.astype(dtype), "wo": wo.astype(dtype)}
    if qkv_bias:
        p["bq"] = jnp.zeros((q_dim,), dtype)
        p["bk"] = jnp.zeros((kv_dim,), dtype)
        p["bv"] = jnp.zeros((kv_dim,), dtype)
    return p


def attention(
    p, x, positions, cfg, *,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None,   # cached (k, v)
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    block_q: int = 512,
    block_k: int = 512,
):
    """Returns (out, (k, v) of *this* call's tokens for cache append)."""
    B, T, D = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("btd,dq->btq", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, T, cfg.eff_q_heads, hd)

    if cross_kv is not None:
        k, v = cross_kv
        new_kv = None
        q = constrain(q, "batch", None, "model", None)
        out = flash_attention_ref(q, k, v, causal=False,
                                  block_q=block_q, block_k=block_k)
    else:
        k = jnp.einsum("btd,dk->btk", x, p["wk"])
        v = jnp.einsum("btd,dk->btk", x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = k.reshape(B, T, cfg.eff_kv_heads, hd)
        v = v.reshape(B, T, cfg.eff_kv_heads, hd)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        new_kv = (k, v)
        q = constrain(q, "batch", None, "model", None)
        k = constrain(k, "batch", None, "model", None)
        v = constrain(v, "batch", None, "model", None)
        if kv is not None:
            # decode: attend over the cache (the new token was already
            # scattered into the cache by the caller)
            k, v = kv
            out = flash_attention_ref(q, k, v, causal=False, window=window,
                                      q_offset=q_offset, block_q=block_q,
                                      block_k=block_k)
        else:
            out = flash_attention_ref(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset, block_q=block_q,
                                      block_k=block_k)

    out = out.reshape(B, T, cfg.eff_q_heads * hd)
    out = jnp.einsum("btq,qd->btd", out, p["wo"])
    return constrain(out, "batch", None, None), new_kv


# ----------------------------------------------------------------------------
# Embedding / loss
# ----------------------------------------------------------------------------

def init_embedding(key, vocab, d_model, dtype, tie, padded_vocab=None):
    k1, k2 = jax.random.split(key)
    pv = padded_vocab or vocab
    p = {"tok": (jax.random.normal(k1, (pv, d_model)) * 0.02).astype(dtype)}
    if not tie:
        p["out"] = (jax.random.normal(k2, (pv, d_model)) * 0.02).astype(dtype)
    return p


def embed(p, tokens):
    return constrain(jnp.take(p["tok"], tokens, axis=0), "batch", None, None)


def unembed(p, x, n_valid: Optional[int] = None):
    w = p.get("out", p["tok"])
    logits = jnp.einsum("btd,vd->btv", x, w)
    if n_valid is not None and n_valid < w.shape[0]:
        vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                             logits.ndim - 1)
        logits = jnp.where(vocab_ids < n_valid, logits, -1e9)
    return logits


def softmax_xent(logits: jax.Array, targets: jax.Array, mask=None) -> jax.Array:
    """Numerically-stable token-mean cross entropy; vocab may be sharded."""
    logits = logits.astype(F32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
