"""Plain float32 forward pass of the dense decoder: the numerics reference
the served path is checked against.

Straightforward jnp with no kernels, no KV cache, no blocking and no
batching. It is written from the llama-family equations, not from the
model code it checks: RMSNorm, rotary embeddings on the two halves of each
head, grouped-query causal softmax attention and a SwiGLU feed-forward.
Weights stay in their stored dtype and are cast to float32 one layer at a
time inside the layer scan, so the reference fits on one chip beside the
served model's weights. Matmuls run at "highest" precision: a TPU otherwise
multiplies float32 operands in bfloat16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotate(x, pos, theta):
    """x (T, H, D): rotary embedding with the (first half, second half)
    pairing of each head's dims."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None, None].astype(F32) * inv_freq       # (T, 1, half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def dense_logits(params, tokens, cfg: ModelConfig):
    """tokens (T,) int32 -> float32 logits (T, vocab_size) at every
    position of one sequence. Dense (llama-family) configs without head
    padding or attention biases."""
    if cfg.family != "dense" or cfg.pad_heads_to or cfg.pad_kv_heads_to \
            or cfg.qkv_bias:
        raise ValueError(f"no plain reference for {cfg.name!r}")
    T = tokens.shape[0]
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]

    def layer(x, lp):
        a = jax.tree.map(lambda w: w.astype(F32), lp["attn"])
        m = jax.tree.map(lambda w: w.astype(F32), lp["mlp"])
        h = _rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = _rotate((h @ a["wq"]).reshape(T, H, D), pos, cfg.rope_theta)
        k = _rotate((h @ a["wk"]).reshape(T, Hkv, D), pos, cfg.rope_theta)
        v = (h @ a["wv"]).reshape(T, Hkv, D)
        q = q.reshape(T, Hkv, H // Hkv, D)      # q head i reads kv head i // R
        s = jnp.einsum("tgrd,sgd->grts", q, k) / jnp.sqrt(F32(D))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("grts,sgd->tgrd", p, v).reshape(T, H * D)
        x = x + o @ a["wo"]
        h = _rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + (jax.nn.silu(h @ m["w1"]) * (h @ m["w3"])) @ m["w2"]
        return x, None

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][tokens].astype(F32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        w_out = params["embed"].get("out", params["embed"]["tok"])
        return (x @ w_out.astype(F32).T)[:, :cfg.vocab_size]
