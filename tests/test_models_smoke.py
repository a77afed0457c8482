"""Per-architecture smoke tests (reduced same-family configs, CPU):
one train step (loss finite, grads flow) + one decode step, and for the
dense family a prefill/decode-vs-full-forward greedy consistency check."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import ShapeConfig
from repro.models import build_model
from repro.models.registry import make_batch

SHAPE = ShapeConfig("smoke", "train", 32, 4)


@pytest.fixture(scope="module")
def smoke_models():
    return {}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_finite(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg, n_groups=1)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = make_batch(cfg, SHAPE)
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss, has_aux=True))(params, batch)
    assert jnp.isfinite(loss), arch
    gnorm = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert gnorm > 0.0 and jnp.isfinite(gnorm), f"{arch}: degenerate grads"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg, n_groups=1)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 4, 32
    cache = model.init_cache(B) if cfg.family == "ssm" else model.init_cache(B, S)
    batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
             "positions": jnp.zeros((B,), jnp.int32)}
    logits, cache2 = jax.jit(model.decode_step)(params, cache, batch)
    assert logits.shape[0] == B and logits.shape[1] == 1
    assert logits.shape[2] >= cfg.vocab_size
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ["llama3-8b", "qwen1.5-32b", "whisper-tiny",
                                  "zamba2-2.7b", "xlstm-350m"])
def test_decode_matches_full_forward(arch):
    """Greedy decode through the cache must equal argmax of the full forward
    at the same position -- catches cache indexing/rope/dequant bugs."""
    cfg = get_config(arch, smoke=True)
    if cfg.kv_cache_dtype == "int8":
        cfg = cfg.replace(kv_cache_dtype="bfloat16")  # exactness for the test
    model = build_model(cfg, n_groups=1)
    params = model.init_params(jax.random.PRNGKey(1))
    B, T = 2, 16
    key = jax.random.PRNGKey(2)
    batch = make_batch(cfg, ShapeConfig("t", "prefill", T, B), key)

    logits_pref, cache = jax.jit(model.prefill)(params, batch)

    # full-forward logits at the last position
    tb = dict(batch)
    tb["targets"] = batch["tokens"]
    # compute full logits through the loss path's forward
    if cfg.family in ("dense", "moe", "vlm"):
        from repro.models import dense as D
        from repro.models import layers as L
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        x = L.embed(params["embed"], batch["tokens"])
        x = D._inject_frontend(params, batch, x, cfg)
        x, _ = D.backbone_fwd(params, x, positions, cfg, n_groups=1,
                              remat=False)
        full_logits = L.unembed(params["embed"], x, cfg.vocab_size)
        ref_next = jnp.argmax(full_logits[:, -1], -1)
        got_next = jnp.argmax(logits_pref[:, -1], -1)
        assert bool(jnp.all(ref_next == got_next)), arch

    # one decode step after prefill must be finite + correctly positioned
    if cfg.family == "ssm":
        cache = model.init_cache(B)
        # rebuild states by decoding the prompt token-by-token
        pos = jnp.zeros((B,), jnp.int32)
        for t in range(T):
            step = {"tokens": batch["tokens"][:, t:t + 1], "positions": pos}
            dec_logits, cache = jax.jit(model.decode_step)(params, cache, step)
            pos = pos + 1
        # final-step logits must match the parallel forward's last position
        logits_par, _ = jax.jit(model.prefill)(params, batch)
        assert jnp.allclose(dec_logits[:, 0], logits_par[:, -1], atol=2e-2,
                            rtol=2e-2), arch
    else:
        step = {"tokens": jnp.argmax(logits_pref[:, -1], -1)[:, None].astype(jnp.int32),
                "positions": jnp.full((B,), T, jnp.int32)}
        dec_logits, _ = jax.jit(model.decode_step)(params, cache, step)
        assert bool(jnp.all(jnp.isfinite(dec_logits.astype(jnp.float32))))


def test_windowed_decode_matches_windowed_forward():
    """With a sliding window, decode steps through the cache attend to the
    keys the windowed full forward attends to at the same positions: the
    window's lower edge falls inside the first of two 512-row key blocks,
    so the decode kernel masks part of a block and skips none it needs."""
    from repro.models import dense as D
    from repro.models import layers as L
    cfg = get_config("llama3-8b", smoke=True).replace(param_dtype="float32")
    params = build_model(cfg, n_groups=1).init_params(jax.random.PRNGKey(5))
    # the forward runs over S tokens (its blocks tile them); being causal,
    # its logits at T..T+n_dec-1 see only the tokens up to each
    B, T, n_dec, S, W = 2, 512, 3, 1024, 24
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0,
                                cfg.vocab_size, jnp.int32)

    def forward(window):
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, _ = D.backbone_fwd(params, L.embed(params["embed"], tokens), pos,
                              cfg, window=window, remat=False)
        return L.unembed(params["embed"], x, cfg.vocab_size)[:, T:T + n_dec]

    want, unwindowed = forward(W), forward(None)
    _, cache = D.lm_prefill(params, {"tokens": tokens[:, :T]}, cfg, window=W)
    cache = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, S - T), (0, 0)]), cache)
    step = jax.jit(lambda c, b: D.lm_decode_step(params, c, b, cfg, window=W))
    for i in range(n_dec):
        batch = {"tokens": tokens[:, T + i:T + i + 1],
                 "positions": jnp.full((B,), T + i, jnp.int32)}
        logits, cache = step(cache, batch)
        got = logits[:, 0].astype(jnp.float32)
        assert jnp.allclose(got, want[:, i], atol=1e-3, rtol=1e-3), i
        # the window changes the answer, so the match is not vacuous
        assert not jnp.allclose(got, unwindowed[:, i], atol=1e-3, rtol=1e-3)


def test_zamba2_decode_consistency_with_prefill_path():
    """Hybrid arch: stepwise decode from scratch equals the parallel
    (chunked-SSD) forward at the final position."""
    cfg = get_config("zamba2-2.7b", smoke=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    B, T = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, T), 0,
                                cfg.vocab_size, jnp.int32)
    logits_par, _ = jax.jit(model.prefill)(params, {"tokens": tokens})

    cache = model.init_cache(B, T)
    pos = jnp.zeros((B,), jnp.int32)
    dec = None
    step_fn = jax.jit(model.decode_step)
    for t in range(T):
        dec, cache = step_fn(params, cache, {"tokens": tokens[:, t:t + 1],
                                             "positions": pos})
        pos = pos + 1
    assert jnp.allclose(dec[:, 0].astype(jnp.float32),
                        logits_par[:, -1].astype(jnp.float32),
                        atol=3e-2, rtol=3e-2)


def test_vocab_padding_is_masked():
    cfg = get_config("whisper-tiny", smoke=True).replace(vocab_size=250)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = make_batch(cfg, SHAPE)
    loss, _ = jax.jit(model.loss)(params, batch)
    assert jnp.isfinite(loss)
    # padded logit rows must be -1e9
    assert cfg.padded_vocab == 256


def test_padded_heads_are_exact():
    """Head padding (qwen/arctic-style) must not change the computed loss:
    init_attention places identically-seeded real weights into the padded
    layout with zero pad heads, preserving the GQA group mapping."""
    base = get_config("llama3-8b", smoke=True)     # 4 q heads, 2 kv heads
    padded = base.replace(pad_heads_to=8)          # R 2 -> 4, grouped pad
    m0, m1 = build_model(base), build_model(padded)
    p0 = m0.init_params(jax.random.PRNGKey(7))
    p1 = m1.init_params(jax.random.PRNGKey(7))
    batch = make_batch(base, SHAPE, jax.random.PRNGKey(8))
    l0, _ = jax.jit(m0.loss)(p0, batch)
    l1, _ = jax.jit(m1.loss)(p1, batch)
    assert jnp.allclose(l0, l1, atol=2e-3, rtol=1e-4), (l0, l1)
