"""Quantized ring all-reduce with error feedback (gradient compression).

A real wire-compression scheme, not an emulation: the ring reduce-scatter
and all-gather move int8 chunks (+ one fp32 scale per chunk) through
lax.ppermute, so on a real fabric each hop transfers ~1/4 of the bf16
bytes. Accumulation happens in fp32 after dequantization at every hop
(standard quantized-ring semantics); the residual between the true local
gradient and its quantized representation is fed back into the next step
(error feedback), which is what keeps SGD/Adam convergence intact.

Usage inside shard_map over the DP axis:
    g_avg, new_err = compressed_psum_mean(g, err, axis_name="data")
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _quant(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(F32)


def _dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(F32) * scale


def compressed_psum_mean(g: jax.Array, err: jax.Array, axis_name: str
                         ) -> Tuple[jax.Array, jax.Array]:
    """Mean-reduce `g` over `axis_name` with int8 ring collectives.

    Must be called inside shard_map/pmap with `axis_name` bound. Returns
    (mean gradient, new error-feedback residual). g is flattened internally;
    the axis size must divide g.size (pad upstream if needed).
    """
    # psum of a concrete 1 constant-folds to the axis size as a python int
    # (jax.lax.axis_size was removed from the public API)
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    flat = (g.astype(F32) + err.astype(F32)).reshape(-1)
    assert flat.size % n == 0, (flat.size, n)
    chunks = flat.reshape(n, -1)

    perm = [(i, (i + 1) % n) for i in range(n)]

    # ---- ring reduce-scatter: after n-1 hops, device d owns the full sum of
    # chunk (d+1) mod n ----------------------------------------------------------
    def rs_step(s, carry):
        acc, send_q, send_scale = carry
        recv_q = jax.lax.ppermute(send_q, axis_name, perm)
        recv_scale = jax.lax.ppermute(send_scale, axis_name, perm)
        # the chunk this device must contribute to at hop s
        chunk_id = (idx - s) % n
        partial_sum = _dequant(recv_q, recv_scale) + chunks[chunk_id]
        q, sc = _quant(partial_sum)
        return (partial_sum, q, sc)

    # hop 0: every device sends its own chunk; at hop s it contributes chunk
    # (idx - s) mod n; after n-1 hops it owns the full sum of (idx+1) mod n
    q0, s0 = _quant(chunks[idx])
    carry = (chunks[idx], q0, s0)
    for s in range(1, n):
        carry = rs_step(s, carry)
    owned_sum, owned_q, owned_scale = carry
    owned_id = (idx - (n - 1)) % n

    # ---- ring all-gather of the quantized owned chunks -------------------------
    gathered_q = jnp.zeros((n,) + owned_q.shape, jnp.int8)
    gathered_s = jnp.zeros((n,), F32)
    gathered_q = gathered_q.at[owned_id].set(owned_q)
    gathered_s = gathered_s.at[owned_id].set(owned_scale)
    send_q, send_s, send_id = owned_q, owned_scale, owned_id
    for _ in range(n - 1):
        send_q = jax.lax.ppermute(send_q, axis_name, perm)
        send_s = jax.lax.ppermute(send_s, axis_name, perm)
        send_id = jax.lax.ppermute(send_id, axis_name, perm)
        gathered_q = gathered_q.at[send_id].set(send_q)
        gathered_s = gathered_s.at[send_id].set(send_s)

    total = _dequant(gathered_q, gathered_s[:, None]).reshape(flat.shape)
    mean = (total / n).reshape(g.shape).astype(g.dtype)

    # ---- error feedback: residual of the local quantized contribution ----------
    # what the ring actually carried for our local data is (approximately) the
    # quantization of (g + err); the residual re-enters next step
    q_local, s_local = _quant(flat)
    carried = _dequant(q_local, s_local)
    new_err = (flat - carried).reshape(g.shape).astype(F32)
    return mean, new_err


def make_compressed_grad_reduce(mesh, axis_name: str):
    """shard_map wrapper: reduce a replicated-per-DP-shard gradient pytree."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def reduce_tree(grads, errs):
        def one(g, e):
            fn = shard_map(
                partial(compressed_psum_mean, axis_name=axis_name),
                mesh=mesh,
                in_specs=(P(axis_name), P(axis_name)),
                out_specs=(P(axis_name), P(axis_name)),
            )
            return fn(g, e)
        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = tdef.flatten_up_to(errs)
        out = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return (tdef.unflatten([o[0] for o in out]),
                tdef.unflatten([o[1] for o in out]))

    return reduce_tree
