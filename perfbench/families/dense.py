"""Benchmark family of a dense (llama-type) decoder: its program config,
seeded weights, plain reference with its fp8 control, and cost counts.

Weights. Seeded random weights in the dtype they are served in, laid out
as the served model (`models/dense.py`) reads them: embeddings padded to a
multiple of 256 rows, layers stacked on a leading axis. Values are uniform
with the served model's own scales (projections d_model ** -0.5,
embeddings 0.02); norm weights are 1 + U(-0.1, 0.1) so a norm whose weight
is dropped shows. Layers are drawn one at a time (`lax.map`) so the
largest temporary is one layer's draw.

Reference. A plain float32 forward pass written from the architecture's
equations and independent of the served code: RMSNorm with a weight,
rotary embeddings on the two halves of each head, grouped-query causal
softmax attention, a SwiGLU feed-forward and an untied (or tied) output
head. It casts each layer to float32 inside the layer scan, attends in
blocks of queries so a 4k context fits beside the weights, and forms
logits only at the positions asked for. `quant="fp8"` is the control
(`reference._matmul`).

Costs. What the algorithm needs, not what the served program happens to
do: a decode step needs every layer's weights, the output head and the KV
entries of the live contexts (not the whole max_len buffer); a token needs
2 flops per weight it multiplies plus causal attention over its context.
The served program may do more; it cannot need less.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _matmul, _rms_norm
from perfbench.weights import _uniform, padded_vocab

Q_BLOCK = 512


def model_config(cfg: Dict[str, Any]):
    """The program's `ModelConfig` from the file's top-level keys."""
    from repro.configs.base import ModelConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in cfg.items() if k in names})


def make_params(key, cfg: Dict[str, Any]):
    dtype = jnp.dtype(cfg["param_dtype"])
    d, f = cfg["d_model"], cfg["d_ff"]
    hd = cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    std = d ** -0.5
    k_emb, k_out, k_layers, k_norm = jax.random.split(key, 4)

    def norm(k, shape):
        return (1.0 + jax.random.uniform(k, shape, jnp.float32, -0.1, 0.1)
                ).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 9)
        return {
            "ln1": norm(ks[0], (d,)),
            "ln2": norm(ks[1], (d,)),
            "attn": {"wq": _uniform(ks[2], (d, q), std, dtype),
                     "wk": _uniform(ks[3], (d, kv), std, dtype),
                     "wv": _uniform(ks[4], (d, kv), std, dtype),
                     "wo": _uniform(ks[5], (q, d), std, dtype)},
            "mlp": {"w1": _uniform(ks[6], (d, f), std, dtype),
                    "w3": _uniform(ks[7], (d, f), std, dtype),
                    "w2": _uniform(ks[8], (f, d), std, dtype)},
        }

    pv = padded_vocab(cfg)
    embed = {"tok": _uniform(k_emb, (pv, d), 0.02, dtype)}
    if not cfg["tie_embeddings"]:
        embed["out"] = _uniform(k_out, (pv, d), 0.02, dtype)
    return {
        "embed": embed,
        "layers": jax.lax.map(layer, jax.random.split(k_layers,
                                                      cfg["n_layers"])),
        "final_norm": norm(k_norm, (d,)),
    }


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
        if cfg.get("qkv_bias"):
            raise ValueError(f"no plain reference for {cfg['name']!r}: "
                             "it has projection biases")
        return cls(cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                   cfg["vocab_size"], float(cfg["rope_theta"]),
                   float(cfg["norm_eps"]))


def _rotate(x, pos, theta):
    """x (T, H, D): rotate (first half, second half) pairs of each head."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None, None].astype(F32) * inv_freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits_at(params, tokens, at, *, dims: Dims, quant: Optional[str]):
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


def logits_at(params, tokens, at, *, cfg: Dict[str, Any],
              quant: Optional[str] = None):
    """tokens (T,) int32, at (P,) int32 -> float32 logits (P, vocab_size):
    row p predicts the token after position at[p]. Causal, so tokens past
    the sequence (padding) leave earlier rows as they are."""
    return _logits_at(params, tokens, at, dims=Dims.of(cfg), quant=quant)


def dtype_bytes(cfg: Dict[str, Any], key: str = "param_dtype") -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[cfg[key]]


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def head_params(cfg: Dict[str, Any]) -> int:
    """The output head's weights over the real vocabulary."""
    return cfg["vocab_size"] * cfg["d_model"]


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """K and V of one position, all layers, one copy of each KV head."""
    return (2 * cfg["n_layers"] * cfg["n_kv_heads"] * cfg["head_dim"]
            * dtype_bytes(cfg, "kv_cache_dtype"))


def decode_step_bytes(cfg: Dict[str, Any], contexts: Iterable[int]) -> int:
    """HBM bytes one batched decode step needs: the layers' weights and
    norms, the final norm and output head, one embedding row per live
    sequence, and the KV entries of each live context (read) plus the new
    position (written)."""
    contexts = list(contexts)
    w = dtype_bytes(cfg)
    d = cfg["d_model"]
    weights = (cfg["n_layers"] * (layer_matmul_params(cfg) + 2 * d)
               + head_params(cfg) + d) * w
    rows = len(contexts) * d * w
    return weights + rows + (sum(contexts) + len(contexts)) \
        * kv_bytes_per_token(cfg)


def attention_flops(cfg: Dict[str, Any], queries_keys: int) -> int:
    """QK^T and PV for `queries_keys` (query, key) pairs, all layers."""
    return 4 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] \
        * queries_keys


def decode_flops(cfg: Dict[str, Any], contexts: Iterable[int]) -> int:
    """One new token for each live sequence of context length c (the
    token itself included)."""
    contexts = list(contexts)
    per_token = 2 * (cfg["n_layers"] * layer_matmul_params(cfg)
                     + cfg["d_model"] * cfg["vocab_size"])
    return len(contexts) * per_token + attention_flops(cfg, sum(contexts))


def prefill_flops(cfg: Dict[str, Any], n: int) -> int:
    """A prompt of n tokens: every layer over every token, causal
    attention, and logits for the last position only."""
    return (2 * n * cfg["n_layers"] * layer_matmul_params(cfg)
            + 2 * cfg["d_model"] * cfg["vocab_size"]
            + attention_flops(cfg, n * (n + 1) // 2))
