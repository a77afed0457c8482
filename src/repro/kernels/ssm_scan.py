"""Pallas TPU chunked SSD (Mamba-2) scan.

One grid step processes one (batch, head, chunk) cell: the intra-chunk
quasi-attention (Q x Q decay-masked scores on the MXU) plus the inter-chunk
contribution from the running state S, which lives in VMEM scratch across
the sequential chunk dimension -- the HBM traffic is x/B/C/dt once, y once,
state never (vs. the jnp reference whose scan carries round-trip every
chunk). This is the TPU-native shape of the SSD algorithm: within-chunk
parallel (MXU), across-chunk recurrent (VMEM-resident).

Layout: x (B,H,T,P); dt (B,H,T); A (H,); Bm/Cm (B,G,T,N).
Out: y (B,H,T,P), final state (B,H,P,N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_out_ref,
                s_ref, *, chunk: int, n_chunks: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0].astype(jnp.float32)        # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)  # (1, Q)
    A = a_ref[h]                               # scalar (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)       # (Q, N)

    # The TPU lowering has no cumsum or 1-D transpose, so the prefix sums
    # are masked (Q, Q) reductions, kept 2-D as a column and as a row.
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col                           # [t, s]: s <= t
    dt_col = jnp.sum(jnp.where(row == col, dt_row, 0.0), axis=1,
                     keepdims=True)            # (Q, 1)
    dA_row, dA_col = dt_row * A, dt_col * A
    la_col = jnp.sum(jnp.where(tri, dA_row, 0.0), axis=1, keepdims=True)
    la_row = jnp.sum(jnp.where(row <= col, dA_col, 0.0), axis=0,
                     keepdims=True)
    la_end = jnp.sum(dA_row, axis=1, keepdims=True)            # (1, 1)

    # intra-chunk: scores[t,s] = (C_t . B_s) * exp(la_t - la_s) * dt_s, s<=t
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    decay = jnp.exp(jnp.clip(la_col - la_row, -60.0, 0.0))
    w_intra = jnp.where(tri, scores * decay, 0.0) * dt_row
    y = jax.lax.dot_general(w_intra, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk from carried state
    S = s_ref[...]                             # (P, N)
    y += jax.lax.dot_general(Cm, S, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * \
        jnp.exp(la_col)

    # state update to chunk end
    w_state = jnp.exp(jnp.clip(la_end - la_col, -60.0, 0.0)) * dt_col
    S_new = jnp.exp(la_end) * S + jax.lax.dot_general(
        x, Bm * w_state, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = S_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        s_out_ref[0, 0] = S_new.astype(s_out_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 256,
             interpret: bool = True):
    """x (B,H,T,P); dt (B,H,T); A (H,); Bm/Cm (B,G,T,N) -> (y, final_state)."""
    B, H, T, P = x.shape
    G, N = Bm.shape[1], Bm.shape[3]
    rep = H // G
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk
    # A is scalar-prefetched into SMEM; dt gets a unit sublane axis so its
    # (1, chunk) block satisfies the TPU tiling rule
    A1 = A.reshape(H).astype(jnp.float32)
    dt4 = dt.reshape(B, H, 1, T)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c, a: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c, a: (b, h, 0, c)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, a: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, a: (b, h // rep, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c, a: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c, a: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    y, s_fin = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(A1, x, dt4, Bm, Cm)
    return y, s_fin
