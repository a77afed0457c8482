"""Dense (llama-family) decoder LM, plus the shared transformer block used by
the MoE / VLM / whisper-decoder families.

Layers are *scanned* (stacked params, `jax.lax.scan`) so the lowered HLO is
one block body regardless of depth -- this keeps 512-device dry-run compiles
fast and is also what production TPU stacks (MaxText et al.) do.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L
from repro.models.moe import init_moe_layer, moe_ffn
from repro.sharding.axes import constrain

F32 = jnp.float32


# ----------------------------------------------------------------------------
# Parameter init
# ----------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype):
    ka, km, kn = jax.random.split(key, 3)
    p: Dict[str, Any] = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": L.init_attention(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.resolved_head_dim, cfg.qkv_bias, dtype,
                                 cfg.pad_heads_to, cfg.pad_kv_heads_to),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe_layer(km, cfg, dtype)
    else:
        std = cfg.d_model ** -0.5
        k1, k2, k3 = jax.random.split(km, 3)
        p["mlp"] = {
            "w1": (jax.random.normal(k1, (cfg.d_model, cfg.d_ff)) * std).astype(dtype),
            "w3": (jax.random.normal(k2, (cfg.d_model, cfg.d_ff)) * std).astype(dtype),
            "w2": (jax.random.normal(k3, (cfg.d_ff, cfg.d_model)) * std).astype(dtype),
        }
    return p


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kl, kf = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_block(k, cfg, dtype))(layer_keys)
    return {
        "embed": L.init_embedding(ke, cfg.vocab_size, cfg.d_model, dtype,
                                  cfg.tie_embeddings, cfg.padded_vocab),
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------

def block_fwd(p, x, positions, cfg: ModelConfig, *, n_groups: int = 1,
              window: Optional[int] = None):
    """Training/prefill block: full-sequence attention + FFN."""
    h, _ = L.attention(p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                       positions, cfg, causal=True, window=window)
    x = x + h
    aux = jnp.zeros((), F32)
    xn = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_ffn(p["moe"], xn, cfg, n_groups)
    else:
        y = L.swiglu(xn, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"])
    # "seq" is unmapped by default; binding it to the model axis turns the
    # per-block TP all-reduces into reduce-scatter+all-gather pairs
    # (Megatron-style sequence parallelism; §Perf it2)
    return constrain(x + y, "batch", "seq", None), aux


def backbone_fwd(params, x, positions, cfg: ModelConfig, *, n_groups: int = 1,
                 window: Optional[int] = None, remat: bool = True):
    """Scan the block stack over stacked layer params. x: (B, T, d)."""
    def body(carry, lp):
        y, aux = block_fwd(lp, carry, positions, cfg, n_groups=n_groups,
                           window=window)
        return y, aux

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, auxs = jax.lax.scan(body, x, params["layers"])
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), jnp.sum(auxs)


def lm_loss(params, batch, cfg: ModelConfig, *, n_groups: int = 1):
    tokens, targets = batch["tokens"], batch["targets"]
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = L.embed(params["embed"], tokens)
    x = _inject_frontend(params, batch, x, cfg)
    x, aux = backbone_fwd(params, x, positions, cfg, n_groups=n_groups)
    logits = L.unembed(params["embed"], x, cfg.vocab_size)
    mask = batch.get("loss_mask")
    loss = L.softmax_xent(logits, targets, mask)
    return loss + aux, {"xent": loss, "aux": aux}


def _inject_frontend(params, batch, x, cfg: ModelConfig):
    """VLM stub frontend: precomputed patch embeddings replace the first
    n_patches token embeddings (the ViT itself is out of scope per spec)."""
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        np_ = pe.shape[1]
        x = jnp.concatenate([pe, x[:, np_:]], axis=1)
    return x


# ----------------------------------------------------------------------------
# KV cache + serving
# ----------------------------------------------------------------------------

def _kv_cache_dtype(cfg: ModelConfig):
    return jnp.int8 if cfg.kv_cache_dtype == "int8" else jnp.dtype(cfg.param_dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """k/v: (layers, batch, max_len, kv_heads * head_dim), a token's heads
    side by side (the layout the decode kernel reads in place); int8 scales:
    (layers, batch, kv_heads, max_len)."""
    hd = cfg.resolved_head_dim
    H = cfg.cache_kv_heads
    shape = (cfg.n_layers, batch, max_len, H * hd)
    kd = _kv_cache_dtype(cfg)
    cache = {"k": jnp.zeros(shape, kd), "v": jnp.zeros(shape, kd)}
    if cfg.kv_cache_dtype == "int8":
        cache["k_scale"] = jnp.zeros((cfg.n_layers, batch, H, max_len), F32)
        cache["v_scale"] = jnp.zeros((cfg.n_layers, batch, H, max_len), F32)
    return cache


def _quantize_kv(x):
    """Per (token, head) symmetric int8 quantization."""
    amax = jnp.max(jnp.abs(x.astype(F32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _cache_entries(cfg: ModelConfig, k, v):
    """(k, v) (..., T, H, hd) in the cache's layout: {"k", "v"} (..., T,
    H*hd), and for int8 caches {"k_scale", "v_scale"} (..., H, T)."""
    if cfg.kv_cache_dtype != "int8":
        return {"k": k.reshape(k.shape[:-2] + (-1,)),
                "v": v.reshape(v.shape[:-2] + (-1,))}
    (kq, ks), (vq, vs) = _quantize_kv(k), _quantize_kv(v)
    heads_first = lambda s: jnp.swapaxes(s[..., 0], -1, -2)
    return {"k": kq.reshape(kq.shape[:-2] + (-1,)),
            "v": vq.reshape(vq.shape[:-2] + (-1,)),
            "k_scale": heads_first(ks), "v_scale": heads_first(vs)}


def block_decode(p, x, cache, layer, pos, cfg: ModelConfig, *,
                 n_groups: int = 1, window: Optional[int] = None):
    """One decode step through one block. x: (B, 1, d); pos: (B,) rows
    already cached. Reads layer `layer` of the stacked cache in place and
    returns this step's (k, v) rows (B, H, hd) for the caller to write."""
    xn = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    hd = cfg.resolved_head_dim
    B = x.shape[0]

    q = jnp.einsum("btd,dq->btq", xn, p["attn"]["wq"])
    k = jnp.einsum("btd,dk->btk", xn, p["attn"]["wk"])
    v = jnp.einsum("btd,dk->btk", xn, p["attn"]["wv"])
    if "bq" in p["attn"]:
        q, k, v = q + p["attn"]["bq"], k + p["attn"]["bk"], v + p["attn"]["bv"]
    # a layout boundary: without it XLA meets the kernel's head-major q and
    # k layouts by transposing wq and wk, a copy of both weights per layer
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    q = L.rope(q.reshape(B, 1, cfg.eff_q_heads, hd), pos[:, None],
               cfg.rope_theta)[:, 0]
    k = L.rope(k.reshape(B, 1, cfg.eff_kv_heads, hd), pos[:, None],
               cfg.rope_theta)[:, 0]
    v = v.reshape(B, cfg.eff_kv_heads, hd)
    if cfg.kv_replication > 1:
        k = jnp.repeat(k, cfg.kv_replication, axis=1)
        v = jnp.repeat(v, cfg.kv_replication, axis=1)

    out = ops.decode_attention(q, k, v, cache["k"], cache["v"], layer, pos,
                               cache.get("k_scale"), cache.get("v_scale"),
                               window=window)
    out = out.reshape(B, 1, cfg.eff_q_heads * hd)
    x = x + jnp.einsum("btq,qd->btd", out, p["attn"]["wo"])

    xn = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, _ = moe_ffn(p["moe"], xn, cfg, n_groups)
    else:
        y = L.swiglu(xn, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"])
    return x + y, (k, v)


def lm_decode_step(params, cache, batch, cfg: ModelConfig, *, n_groups: int = 1,
                   window: Optional[int] = None):
    """One-token decode across the whole stack.

    The layer scan only reads the stacked cache: it is a loop-invariant
    operand, and the attention kernel fetches layer `li`'s live blocks from
    it in place. Each layer emits its new (k, v) row; one scatter after the
    scan writes them all into the (donated) cache, one row per slot per
    layer, dropping a row at or past max_len."""
    tokens, pos = batch["tokens"], batch["positions"]
    x = L.embed(params["embed"], tokens)

    def body(carry, lp):
        x_c, li = carry
        y, rows = block_decode(lp, x_c, cache, li, pos, cfg,
                               n_groups=n_groups, window=window)
        return (y, li + 1), rows

    (x, _), (k, v) = jax.lax.scan(body, (x, jnp.zeros((), jnp.int32)),
                                  params["layers"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg.vocab_size)
    n_layers, B = k.shape[:2]
    layer = jnp.repeat(jnp.arange(n_layers), B)          # one row per (l, b)
    slot = jnp.tile(jnp.arange(B), n_layers)
    row = jnp.tile(pos, n_layers)
    new_cache = {}
    for name, rows in _cache_entries(cfg, k[:, :, None], v[:, :, None]).items():
        c = cache[name]
        if name.endswith("_scale"):     # (L, B, H, 1) into (L, B, H, S)
            # one element each: written as H-wide rows, XLA would lay the
            # whole scale array out anew (H minor) and back every step
            H = c.shape[2]
            c = c.at[jnp.repeat(layer, H), jnp.repeat(slot, H),
                     jnp.tile(jnp.arange(H), n_layers * B),
                     jnp.repeat(row, H)].set(rows.reshape(-1), mode="drop")
        else:                           # (L, B, 1, H*hd) into (L, B, S, H*hd)
            c = c.at[layer, slot, row].set(
                rows.reshape(n_layers * B, -1).astype(c.dtype), mode="drop")
        new_cache[name] = c
    return logits, new_cache


def lm_prefill(params, batch, cfg: ModelConfig, *, n_groups: int = 1,
               window: Optional[int] = None):
    """Prefill: full forward that also materializes the KV cache.

    Returns (last-token logits, cache). Cache buffers sized to seq_len.
    """
    tokens = batch["tokens"]
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = L.embed(params["embed"], tokens)
    x = _inject_frontend(params, batch, x, cfg)

    def body(carry, lp):
        xc = carry
        xn = L.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        h, kv = L.attention(lp["attn"], xn, positions, cfg, causal=True,
                            window=window)
        xc = xc + h
        xn = L.rms_norm(xc, lp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = moe_ffn(lp["moe"], xn, cfg, n_groups)
        else:
            y = L.swiglu(xn, lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"])
        xc = xc + y
        k, v = kv
        if cfg.kv_replication > 1:
            k = jnp.repeat(k, cfg.kv_replication, axis=2)
            v = jnp.repeat(v, cfg.kv_replication, axis=2)
        return xc, _cache_entries(cfg, k, v)

    x, cache = jax.lax.scan(body, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x[:, -1:, :], cfg.vocab_size)
    return logits, cache
