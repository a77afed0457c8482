"""Operations and bytes a dense (llama-type) model needs, from its shapes.

These count what the algorithm needs, not what the served program happens
to do: a decode step needs every layer's weights, the output head and the
KV entries of the live contexts (not the whole max_len buffer); a token
needs 2 flops per weight it multiplies plus causal attention over its
context. The served program may do more; it cannot need less.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def head_params(cfg: Dict[str, Any]) -> int:
    """The output head's weights over the real vocabulary."""
    return cfg["vocab_size"] * cfg["d_model"]


def dtype_bytes(cfg: Dict[str, Any], key: str = "param_dtype") -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[cfg[key]]


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
