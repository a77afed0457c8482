"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + property tests.
All kernels run in interpret mode on CPU (the kernel body itself executes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover -- bare container without dev deps
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------- flash attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,T,D,bq,bk", [
    (1, 2, 2, 64, 32, 32, 32),        # MHA
    (2, 4, 2, 128, 64, 64, 32),       # GQA 2:1
    (1, 8, 2, 128, 32, 32, 64),       # GQA 4:1, uneven blocks
    (2, 2, 1, 256, 16, 128, 128),     # long-ish
])
def test_flash_attention_sweep(B, Hq, Hkv, T, D, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * T + Hq), 3)
    q = _rand(ks[0], (B, Hq, T, D), dtype)
    k = _rand(ks[1], (B, Hkv, T, D), dtype)
    v = _rand(ks[2], (B, Hkv, T, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_window():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (1, 2, 128, 32), jnp.float32)
    k = _rand(ks[1], (1, 2, 128, 32), jnp.float32)
    v = _rand(ks[2], (1, 2, 128, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=32,
                              block_q=32, block_k=32)
    want = ref.attention_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (2, 2, 64, 32), jnp.float32)
    k = _rand(ks[1], (2, 2, 64, 32), jnp.float32)
    v = _rand(ks[2], (2, 2, 64, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([32, 64]), st.sampled_from([1, 2, 4]),
       st.sampled_from([16, 32]))
def test_flash_attention_property(T, R, D):
    """Property: GQA folding matches explicit KV repetition."""
    Hkv = 2
    ks = jax.random.split(jax.random.PRNGKey(T * R + D), 3)
    q = _rand(ks[0], (1, Hkv * R, T, D), jnp.float32)
    k = _rand(ks[1], (1, Hkv, T, D), jnp.float32)
    v = _rand(ks[2], (1, Hkv, T, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, block_q=T // 2,
                              block_k=T // 2)
    krep = jnp.repeat(k, R, axis=1)
    vrep = jnp.repeat(v, R, axis=1)
    want = ref.attention_ref(q, krep, vrep, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


# ------------------------------------------------------------- decode attention

def _stacked_cache(key, L, B, S, Hkv, D, dtype):
    """A random stacked cache (L,B,S,Hkv*D) as (k, v, k_scale, v_scale,
    k_float, v_float): int8 comes with its per-(token, head) scales
    (L,B,Hkv,S), and the float32 values it was made from."""
    ks = jax.random.split(key, 2)
    kf = jax.random.normal(ks[0], (L, B, S, Hkv, D), jnp.float32)
    vf = jax.random.normal(ks[1], (L, B, S, Hkv, D), jnp.float32)
    flat = lambda x: x.reshape(L, B, S, Hkv * D)
    if dtype != jnp.int8:
        return (flat(kf).astype(dtype), flat(vf).astype(dtype), None, None,
                flat(kf), flat(vf))
    out = []
    for x in (kf, vf):
        sc = jnp.max(jnp.abs(x), -1) / 127.0
        q8 = jnp.round(x / sc[..., None]).astype(jnp.int8)
        out.append((flat(q8), sc.transpose(0, 1, 3, 2)))
    (k8, ksc), (v8, vsc) = out
    return k8, v8, ksc, vsc, flat(kf), flat(vf)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (2, 4, 2, 128, 32),
    (1, 8, 8, 256, 64),
    (3, 2, 1, 64, 16),
])
def test_decode_attention_sweep(B, Hq, Hkv, S, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + Hq), 4)
    q = _rand(ks[0], (B, Hq, D), dtype)
    kn = _rand(ks[1], (B, Hkv, D), dtype)
    vn = _rand(ks[2], (B, Hkv, D), dtype)
    k, v, *_ = _stacked_cache(ks[3], 2, B, S, Hkv, D, dtype)
    pos = jnp.arange(1, B + 1) * (S // (B + 1)) + 1
    out = ops.decode_attention(q, kn, vn, k, v, 1, pos, block_k=S // 2)
    want = ref.decode_attention_ref(q, kn, vn, k, v, 1, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_int8_cache():
    B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = _rand(ks[0], (B, Hq, D), jnp.float32)
    kn = _rand(ks[1], (B, Hkv, D), jnp.float32)
    vn = _rand(ks[2], (B, Hkv, D), jnp.float32)
    k8, v8, ksc, vsc, kf, vf = _stacked_cache(ks[3], 1, B, S, Hkv, D,
                                              jnp.int8)
    pos = jnp.array([64, 127])
    out = ops.decode_attention(q, kn, vn, k8, v8, 0, pos, ksc, vsc,
                               block_k=64)
    want = ref.decode_attention_ref(q, kn, vn, k8, v8, 0, pos,
                                    k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # and the dequantized result is close to the fp32 attention over the
    # values the cache was quantized from
    exact = ref.decode_attention_ref(q, kn, vn, kf, vf, 0, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exact),
                               atol=0.05, rtol=0.05)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("Hq,Hkv", [(8, 2), (4, 4)], ids=["gqa4", "mha"])
def test_decode_attention_reads_stacked_cache(Hq, Hkv, cache_dtype, window):
    """The kernel on the stacked (L,B,S,Hkv*D) cache at layer 2 equals
    `flash_attention_ref` over each slot's cached rows plus the step's own
    row: pos at 0, at a block edge, inside a block, one below max_len and
    at max_len (the new row is not cached, only attended)."""
    from repro.models.layers import flash_attention_ref
    L, S, D, bk = 3, 64, 32, 16
    pos = jnp.array([0, bk, 2 * bk + 5, S - 1, S], jnp.int32)
    B = pos.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(Hq * 7 + Hkv), 4)
    q = _rand(ks[0], (B, Hq, D), jnp.bfloat16)
    kn = _rand(ks[1], (B, Hkv, D), jnp.bfloat16)
    vn = _rand(ks[2], (B, Hkv, D), jnp.bfloat16)
    k, v, ksc, vsc, *_ = _stacked_cache(ks[3], L, B, S, Hkv, D,
                                        cache_dtype)
    out = ops.decode_attention(q, kn, vn, k, v, 2, pos, ksc, vsc,
                               window=window, block_k=bk)

    def rows(cache, scale, b):          # layer 2, slot b, as float32
        x = cache[2, b].reshape(S, Hkv, D).astype(jnp.float32)
        return x if scale is None else x * scale[2, b].T[..., None]

    for b in range(B):
        p = int(pos[b])
        lo = 0 if window is None else max(0, p - window + 1)
        hi = min(p, S)
        kr = jnp.concatenate([rows(k, ksc, b)[lo:hi],
                              kn[b, None].astype(jnp.float32)])
        vr = jnp.concatenate([rows(v, vsc, b)[lo:hi],
                              vn[b, None].astype(jnp.float32)])
        want = flash_attention_ref(
            q[b, None, None].astype(jnp.float32), kr[None], vr[None],
            causal=False, block_q=1, block_k=kr.shape[0],
            valid_len=jnp.array([kr.shape[0]]))
        np.testing.assert_allclose(np.asarray(out[b], np.float32),
                                   np.asarray(want[0, 0]),
                                   atol=2e-2, rtol=2e-2)


# ------------------------------------------------------------- grouped matmul

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,d,f,bc,bd,bf", [
    (2, 64, 128, 64, 32, 64, 32),
    (4, 32, 64, 128, 32, 32, 64),
    (8, 16, 32, 32, 16, 32, 32),
])
def test_moe_gmm_sweep(E, C, d, f, bc, bd, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(E + C), 2)
    x = _rand(ks[0], (E, C, d), dtype)
    w = _rand(ks[1], (E, d, f), dtype)
    out = ops.moe_gmm(x, w, block_c=bc, block_d=bd, block_f=bf)
    want = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


# ------------------------------------------------------------- SSD scan

@pytest.mark.parametrize("B,H,T,P,G,N,chunk", [
    (1, 2, 64, 16, 1, 8, 16),
    (2, 4, 64, 32, 2, 16, 32),
    (1, 2, 128, 16, 2, 8, 16),
])
def test_ssd_scan_vs_sequential(B, H, T, P, G, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(T + P), 5)
    x = _rand(ks[0], (B, H, T, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(ks[1], (B, H, T), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (H,), jnp.float32) * 0.3)
    Bm = _rand(ks[3], (B, G, T, N), jnp.float32) * 0.5
    Cm = _rand(ks[4], (B, G, T, N), jnp.float32) * 0.5
    y, s_fin = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    want = ref.ssd_chunk_ref(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1),
                             A, Bm.transpose(0, 2, 1, 3),
                             Cm.transpose(0, 2, 1, 3), chunk)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(want.transpose(0, 2, 1, 3)),
                               atol=2e-4, rtol=2e-4)
    assert s_fin.shape == (B, H, P, N)


@settings(max_examples=6, deadline=None)
@given(st.sampled_from([16, 32]), st.sampled_from([16, 32, 64]))
def test_ssd_chunk_invariance(chunk, T2):
    """Property: the chunked result is invariant to the chunk size."""
    if T2 % chunk:
        return
    B, H, P, G, N = 1, 2, 16, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(chunk * T2), 5)
    x = _rand(ks[0], (B, H, T2, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(ks[1], (B, H, T2), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (H,), jnp.float32) * 0.3)
    Bm = _rand(ks[3], (B, G, T2, N), jnp.float32) * 0.5
    Cm = _rand(ks[4], (B, G, T2, N), jnp.float32) * 0.5
    y1, _ = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y2, _ = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=T2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=2e-4)


# ------------------------------------------------------------- model-internal chunked forms

def test_mlstm_chunked_matches_sequential():
    from repro.models.xlstm import _mlstm_chunked
    B, T, H, Dh = 2, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = _rand(ks[0], (B, T, H, Dh), jnp.float32)
    k = _rand(ks[1], (B, T, H, Dh), jnp.float32)
    v = _rand(ks[2], (B, T, H, Dh), jnp.float32)
    ig = _rand(ks[3], (B, T, H), jnp.float32)
    fg = _rand(ks[4], (B, T, H), jnp.float32) + 3.0
    lf = jax.nn.log_sigmoid(fg)
    got = _mlstm_chunked(q, k, v, ig, lf, chunk=16)
    want = ref.mlstm_ref(q.transpose(0, 1, 2, 3), k, v, ig, lf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-4, rtol=3e-4)


def test_flash_ref_valid_len_masking():
    """layers.flash_attention_ref per-batch validity (decode masking)."""
    from repro.models.layers import flash_attention_ref
    B, T, H, D = 2, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(ks[0], (B, 1, H, D), jnp.float32)
    k = _rand(ks[1], (B, T, H, D), jnp.float32)
    v = _rand(ks[2], (B, T, H, D), jnp.float32)
    vl = jnp.array([5, 64])
    got = flash_attention_ref(q, k, v, causal=False, valid_len=vl,
                              block_q=1, block_k=16)
    want = ref.attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=False,
                             valid_len=vl).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
