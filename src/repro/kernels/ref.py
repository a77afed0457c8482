"""Pure-jnp oracles for every Pallas kernel (exact, unblocked math).

Tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal=True, window: Optional[int] = None,
                  valid_len=None, kv_scale=None, v_scale=None):
    """q (B,Hq,Tq,D); k/v (B,Hkv,Tk,D) [+ optional int8 scales (B,Hkv,Tk,1)]."""
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    R = Hq // Hkv
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if kv_scale is not None:
        kf = kf * kv_scale
    if v_scale is not None:
        vf = vf * v_scale
    kf = jnp.repeat(kf, R, axis=1)
    vf = jnp.repeat(vf, R, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) / math.sqrt(D)
    qpos = jnp.arange(Tq)[:, None] + (Tk - Tq)   # align ends (decode offset)
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= kpos > qpos - window
    mask = jnp.broadcast_to(mask, (B, 1, Tq, Tk))
    if valid_len is not None:
        mask = mask & (kpos[None, None] < valid_len[:, None, None, None])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def decode_attention_ref(q, k_new, v_new, k_cache, v_cache, layer, pos, *,
                         k_scale=None, v_scale=None,
                         window: Optional[int] = None):
    """Decode attention oracle: q (B,Hq,D) over layer `layer` of the stacked
    cache (L,B,S,Hkv*D) [+ int8 scales (L,B,Hkv,S)], rows [pos - window + 1,
    min(pos, S)), plus the step's own k_new/v_new (B,Hkv,D) at pos."""
    B, Hq, D = q.shape
    Hkv = k_new.shape[1]
    S = k_cache.shape[2]
    f32 = jnp.float32
    kc = k_cache[layer].reshape(B, S, Hkv, D).astype(f32)
    vc = v_cache[layer].reshape(B, S, Hkv, D).astype(f32)
    if k_scale is not None:
        kc = kc * k_scale[layer].transpose(0, 2, 1)[..., None]
        vc = vc * v_scale[layer].transpose(0, 2, 1)[..., None]
    k = jnp.concatenate([kc, k_new[:, None].astype(f32)], axis=1)
    v = jnp.concatenate([vc, v_new[:, None].astype(f32)], axis=1)
    kpos = jnp.arange(S + 1)[None, :]
    pos = pos[:, None]
    keep = kpos < jnp.minimum(pos, S)
    if window is not None:
        keep &= kpos > pos - window
    keep |= kpos == S                                   # the new row
    qg = q.reshape(B, Hkv, Hq // Hkv, D).astype(f32)
    s = jnp.einsum("bhrd,bkhd->bhrk", qg, k) / math.sqrt(D)
    s = jnp.where(keep[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrk,bkhd->bhrd", p, v)
    return out.reshape(B, Hq, D).astype(q.dtype)


def moe_gmm_ref(x, w):
    """Grouped matmul oracle. x (E, C, d) @ w (E, d, f) -> (E, C, f)."""
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


def ssd_chunk_ref(x, dt, A, Bm, Cm, chunk):
    """Chunked-SSD oracle via the *sequential* recurrence (ground truth).

    x (B,T,H,P); dt (B,T,H); A (H,); Bm/Cm (B,T,G,N) -> y (B,T,H,P).
    """
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        dA = jnp.exp(dtt * A)                         # (B,H)
        S = dA[:, :, None, None] * S + jnp.einsum("bh,bhp,bhn->bhpn", dtt, xt, Bt)
        y = jnp.einsum("bhn,bhpn->bhp", Ct, S)
        return S, y

    S0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    xs = (xf.transpose(1, 0, 2, 3), dtf.transpose(1, 0, 2),
          Bh.transpose(1, 0, 2, 3), Ch.transpose(1, 0, 2, 3))
    _, ys = jax.lax.scan(step, S0, xs)
    return ys.transpose(1, 0, 2, 3)


def mlstm_ref(q, k, v, ig, lf):
    """Sequential stabilized mLSTM recurrence (ground truth).

    q/k/v (B,T,H,Dh); ig/lf (B,T,H) (input-gate preact, log-sigmoid forget).
    """
    B, T, H, Dh = q.shape
    scale = Dh ** -0.5

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, it, ft = inp
        m_new = jnp.maximum(ft + m, it)
        fp = jnp.exp(ft + m - m_new)
        ip = jnp.exp(it - m_new)
        C = fp[..., None, None] * C + ip[..., None, None] * \
            jnp.einsum("bhd,bhe->bhde", kt, vt)
        n = fp[..., None] * n + ip[..., None] * kt
        num = jnp.einsum("bhd,bhde->bhe", qt * scale, C)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qt * scale, n)),
                          jnp.exp(-m_new))
        return (C, n, m_new), num / den[..., None]

    C0 = jnp.zeros((B, H, Dh, Dh), jnp.float32)
    n0 = jnp.zeros((B, H, Dh), jnp.float32)
    m0 = jnp.full((B, H), -30.0, jnp.float32)
    xs = tuple(a.astype(jnp.float32).transpose(1, 0, *range(2, a.ndim))
               for a in (q, k, v, ig, lf))
    _, hs = jax.lax.scan(step, (C0, n0, m0), xs)
    return hs.transpose(1, 0, 2, 3)
