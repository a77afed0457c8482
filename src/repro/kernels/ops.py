"""jit'd public wrappers over the Pallas kernels.

The kernels are compiled for the TPU. On the CPU backend, and only there,
they run in Pallas interpret mode (the kernel body executes as traced jnp
ops); the choice is made while tracing, from the backend. The jnp oracles
in kernels/ref.py are the numerics reference -- tests assert allclose
across shape and dtype sweeps.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.moe_gmm import moe_gmm as _gmm
from repro.kernels.ssm_scan import ssd_scan as _ssd


def _interpret() -> bool:
    """Interpret mode on the CPU backend only; compiled everywhere else."""
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=256, block_k=256):
    """q (B,Hq,T,D); k/v (B,Hkv,T,D)."""
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=_interpret())


@partial(jax.jit, static_argnames=("window", "block_k"))
def decode_attention(q, k_new, v_new, k_cache, v_cache, layer, pos,
                     k_scale=None, v_scale=None, *, window=None, block_k=None):
    """q (B,Hq,D); this step's k_new/v_new (B,Hkv,D); the stacked cache
    (L,B,S,Hkv*D) [+int8 scales (L,B,Hkv,S)]; layer; pos (B,)."""
    return _decode(q, k_new, v_new, k_cache, v_cache, layer, pos,
                   k_scale=k_scale, v_scale=v_scale, window=window,
                   block_k=block_k, interpret=_interpret())


@partial(jax.jit, static_argnames=("block_c", "block_d", "block_f"))
def moe_gmm(x, w, *, block_c=128, block_d=512, block_f=256):
    """Grouped expert matmul: (E,C,d) @ (E,d,f) -> (E,C,f)."""
    return _gmm(x, w, block_c=block_c, block_d=block_d, block_f=block_f,
                interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk=256):
    """Mamba2 SSD: x (B,H,T,P), dt (B,H,T), A (H,), Bm/Cm (B,G,T,N)."""
    return _ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=_interpret())


__all__ = ["flash_attention", "decode_attention", "moe_gmm", "ssd_scan", "ref"]
