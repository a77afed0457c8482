"""Pieces the plain references share. Each family's reference
(`families/<family>.py:logits_at`) is a float32 forward pass written from
its architecture's equations, independent of the served code, on the
benchmark's own weights. On a TPU a float32 matmul runs in bfloat16 passes
unless the reference sets "highest" precision, so each one does.

`quant="fp8"` is the control, the step below the bfloat16 the
configurations state, which the comparison must refuse: every projection
and the output head multiply weights rounded to float8_e4m3fn per output
channel by activations rounded per row (absmax scaled to 448), the
rounding emulated and the product taken in float32 (`_matmul`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


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
