"""Seeded random weights for a dense (llama-type) configuration, made on
the device in one jitted call, in the dtype they are served in.

The tree is laid out as the served model (`models/dense.py`) reads it:
embeddings padded to a multiple of 256 rows, layers stacked on a leading
axis. Values are uniform with the served model's own scales (projections
d_model ** -0.5, embeddings 0.02); norm weights are 1 + U(-0.1, 0.1) so a
norm whose weight is dropped shows. Layers are drawn one at a time
(`lax.map`) so the largest temporary is one layer's draw.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding


def key32(seed: int) -> int:
    """Any whole seed, however large, as 32 bits for the device's RNG."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def padded_vocab(cfg: Dict[str, Any]) -> int:
    return -(-int(cfg["vocab_size"]) // 256) * 256


def _uniform(key, shape, std, dtype):
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


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


def init_on_device(cfg: Dict[str, Any], seed: int, device):
    """The weights of `seed`, committed to `device`."""
    fn = jax.jit(lambda k: make_params(k, cfg),
                 out_shardings=SingleDeviceSharding(device))
    return fn(jax.random.key(key32(seed), impl="rbg"))
