"""Plain float32 forward pass of a dense (llama-type) decoder: the oracle the
served tokens are held to, and its lower-precision control.

Written from the architecture's equations and independent of the served
code: RMSNorm with a weight, rotary embeddings on the two halves of each
head, grouped-query causal softmax attention, a SwiGLU feed-forward and an
untied (or tied) output head. It takes the weights the benchmark made from
the seed, in the served layout, casts each layer to float32 inside the
layer scan, attends in blocks of queries so a 4k context fits beside the
weights, and forms logits only at the positions asked for. Matmuls run at
"highest" precision: a TPU otherwise multiplies float32 in bfloat16 passes.

`quant="fp8"` is the control, the step below the bfloat16 the
configurations state, which the comparison must refuse: every projection
and the output head multiply weights rounded to float8_e4m3fn per output
channel by activations rounded per row (absmax scaled to 448), the
rounding emulated and the product taken in float32.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512


@dataclass(frozen=True)
class Dims:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def of(cls, cfg: Dict[str, Any]) -> "Dims":
        if cfg["family"] != "dense" or cfg.get("qkv_bias"):
            raise ValueError(f"no plain reference for {cfg['name']!r}")
        return cls(cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                   cfg["vocab_size"], float(cfg["rope_theta"]),
                   float(cfg["norm_eps"]))


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotate(x, pos, theta):
    """x (T, H, D): rotate (first half, second half) pairs of each head."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None, None].astype(F32) * inv_freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 448
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _matmul(h, w, quant: Optional[str]):
    """h (T, i) @ w (i, o), both float32."""
    if quant is None:
        return h @ w
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    return _fp8(h, 1) @ _fp8(w, 0)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def logits_at(params, tokens, at, *, dims: Dims, quant: Optional[str] = None):
    """tokens (T,) int32, at (P,) int32 -> float32 logits (P, vocab_size):
    row p predicts the token after position at[p]. Causal, so tokens past
    the sequence (padding) leave earlier rows as they are."""
    T = tokens.shape[0]
    H, Hkv, D = dims.n_heads, dims.n_kv_heads, dims.head_dim
    G = H // Hkv
    pos = jnp.arange(T)
    nq = T // Q_BLOCK if T % Q_BLOCK == 0 and T > Q_BLOCK else 1
    bq = T // nq

    def attend(q, k, v):
        """q (T, Hkv, G, D) over k, v (T, Hkv, D), in blocks of queries."""
        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 0)
            qpos = i * bq + jnp.arange(bq)
            s = jnp.einsum("tgrd,sgd->grts", qb, k) / jnp.sqrt(F32(D))
            s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("grts,sgd->tgrd", p, v)
        return jax.lax.map(block, jnp.arange(nq)).reshape(T, H * D)

    def layer(x, lp):
        a = jax.tree.map(lambda w: w.astype(F32), lp["attn"])
        m = jax.tree.map(lambda w: w.astype(F32), lp["mlp"])
        h = _rms_norm(x, lp["ln1"], dims.norm_eps)
        q = _rotate(_matmul(h, a["wq"], quant).reshape(T, H, D), pos,
                    dims.rope_theta)
        k = _rotate(_matmul(h, a["wk"], quant).reshape(T, Hkv, D), pos,
                    dims.rope_theta)
        v = _matmul(h, a["wv"], quant).reshape(T, Hkv, D)
        o = attend(q.reshape(T, Hkv, G, D), k, v)   # q head i reads kv i // G
        x = x + _matmul(o, a["wo"], quant)
        h = _rms_norm(x, lp["ln2"], dims.norm_eps)
        g = jax.nn.silu(_matmul(h, m["w1"], quant)) * _matmul(h, m["w3"], quant)
        return x + _matmul(g, m["w2"], quant), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][tokens].astype(F32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms_norm(x[at], params["final_norm"], dims.norm_eps)
        w_out = params["embed"].get("out", params["embed"]["tok"])
        w_out = w_out[:dims.vocab_size].astype(F32)
        return _matmul(x, w_out.T, quant)
