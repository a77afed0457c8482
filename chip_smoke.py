#!/usr/bin/env python3
"""Smoke test of the served path on a TPU: llama3-8b behind the router.

The model is llama3-8b at its published widths (d_model 4096, 32 query
and 8 KV heads, d_ff 14336, vocab 128256) with random weights drawn from
a seed and its depth cut so that one 16 GB v5e chip holds the weights,
the KV cache and the cache copy that admitting a request makes. Requests
take the whole served path, all in this one process:

    Router -> ActorReplicaHandle -> head (actor_call) -> worker thread
           -> ReplicaActor -> ServeEngine -> chip

Phases (one chip, the default):
  kernels  each Pallas kernel in `repro.kernels.ops`, compiled for the chip,
           against its jnp oracle in `repro.kernels.ref`;
  serve    seeded requests of two prompt lengths, more than the engine has
           slots, all complete; each prompt's prefill logits and each
           request's first decode-step logits agree with a plain float32
           forward of the same weights (`repro.models.reference`).

`--chips 4` runs only the replica-fleet phase: one replica per chip, each
engine's weights, cache and inputs on its own device, serving the same
requests as a single replica on chip 0 with matching first-token logits.

Exits nonzero, with no result line, when JAX finds no TPU or a phase
fails. The last line of stdout is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four one-chip replicas
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import threading
import time
import traceback
from pathlib import Path
from typing import Any, Callable, Dict, List, Sequence, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.reference import dense_logits  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.serve.fleet import serve_fleet  # noqa: E402

SEED = 0
# The cut, from the compiled programs' memory analysis on a v5e: 16 of 32
# layers is 9.1 GB of bf16 weights with the embeddings; 8 slots x 1024
# positions x 16 cache heads (kv_replication 2) is a 1.1 GB KV cache, and
# admission copies it once more.
LAYERS, SLOTS, MAX_LEN = 16, 8, 1024
PROMPT_LENS = (32, 96)
N_REQUESTS = 12                  # > SLOTS: admission refills freed slots
MAX_NEW = (4, 10)                # per-request decode budget, inclusive
SEQ_LEN = 2048                   # kernel phase: prefill-attention length
# Served logits (bf16 weights and activations) against the float32
# reference, as a relative L2 error. bf16 rounding alone gives a few
# percent and grows with depth (XLA CPU at reduced width: 4 layers 2%,
# 16 layers 3.4%, 32 layers 4.5%; TPU v5e at full width, 16 layers:
# 2.9%); a wrong cache slot, position or kernel is off by order 1.
LOGIT_TOL = 0.1
KERNEL_TOL = 2e-2                # kernel vs oracle, same measure
REPLICA_TOL = 1e-3               # same program on two chips


def device_info() -> Dict[str, Any]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def rel_err(got, want) -> float:
    """|got - want| / |want| (L2 norms), in float32."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def phase(name: str, fn: Callable[[], bool]) -> bool:
    t0 = time.perf_counter()
    try:
        ok = bool(fn())
    except Exception:  # noqa: BLE001 -- a phase failure is a result
        traceback.print_exc()
        ok = False
    print(f"[{name}] {'ok' if ok else 'FAILED'} "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)
    return ok


# ---------------------------------------------------------------- kernels


def kernel_cases(dense: ModelConfig, moe: ModelConfig, ssm: ModelConfig,
                 seq_len: int, slots: int, max_len: int, seed: int = SEED
                 ) -> Dict[str, Tuple[Any, tuple, dict, Callable]]:
    """name -> (jitted kernel, args, static kwargs, oracle), with widths
    from the configs: attention from `dense`, the grouped matmul from
    `moe`'s experts, the SSD scan from `ssm`'s mamba2 heads."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def rand(shape, dtype=bf16, scale=1.0):
        return (jax.random.normal(next(ks), shape, f32) * scale).astype(dtype)

    hd, hq = dense.resolved_head_dim, dense.n_heads
    q = rand((1, hq, seq_len, hd))
    k = rand((1, dense.n_kv_heads, seq_len, hd))
    v = rand((1, dense.n_kv_heads, seq_len, hd))
    hkv = dense.cache_kv_heads
    dq = rand((slots, hq, hd))
    dkn, dvn = rand((slots, hkv, hd)), rand((slots, hkv, hd))
    dk = rand((2, slots, max_len, hkv * hd))      # two stacked layers
    dv = rand((2, slots, max_len, hkv * hd))
    layer = jnp.int32(1)
    pos = jnp.linspace(0, max_len, slots).astype(jnp.int32)
    e = moe.moe.n_experts
    gx = rand((e, min(128, seq_len), moe.d_model))
    gw = rand((e, moe.d_model, moe.d_ff), scale=moe.d_model ** -0.5)
    s = ssm.ssm
    H = s.expand * ssm.d_model // s.head_dim
    x = rand((1, H, seq_len, s.head_dim), f32, 0.5)
    dt = jax.nn.softplus(rand((1, H, seq_len), f32))
    A = -jnp.exp(rand((H,), f32, 0.3))
    Bm = rand((1, s.n_groups, seq_len, s.d_state), f32, 0.5)
    Cm = rand((1, s.n_groups, seq_len, s.d_state), f32, 0.5)
    chunk = min(s.chunk_size, seq_len)
    t = lambda a: a.transpose(0, 2, 1, 3)
    return {
        "flash_attention": (
            ops.flash_attention, (q, k, v), {},
            lambda: ref.attention_ref(q, k, v, causal=True)),
        "decode_attention": (
            ops.decode_attention, (dq, dkn, dvn, dk, dv, layer, pos), {},
            lambda: ref.decode_attention_ref(dq, dkn, dvn, dk, dv, layer,
                                             pos)),
        "moe_gmm": (ops.moe_gmm, (gx, gw), {},
                    lambda: ref.moe_gmm_ref(gx, gw)),
        "ssd_scan": (
            ops.ssd_scan, (x, dt, A, Bm, Cm), {"chunk": chunk},
            lambda: t(ref.ssd_chunk_ref(t(x), dt.transpose(0, 2, 1), A,
                                        t(Bm), t(Cm), chunk))),
    }


def kernel_phase(cases, *, need_compiled: bool, tol: float = KERNEL_TOL
                 ) -> bool:
    """Run each kernel once and hold it to its oracle. `need_compiled`
    also requires a Mosaic kernel in the compiled program: on the chip
    nothing may run in the Pallas interpreter."""
    ok = True
    for name, (fn, args, kw, oracle) in cases.items():
        compiled = fn.lower(*args, **kw).compile()
        native = "tpu_custom_call" in compiled.as_text()
        out = compiled(*args)
        out = out[0] if isinstance(out, tuple) else out
        with jax.default_matmul_precision("highest"):
            err = rel_err(out, oracle())
        good = err <= tol and (native or not need_compiled)
        ok &= good
        shapes = " ".join("x".join(map(str, a.shape)) for a in args)
        print(f"  {name:<17} {shapes:<48} "
              f"{'compiled' if native else 'interpreted':<11} "
              f"err {err:.2e} (tol {tol:.0e}) {'ok' if good else 'BAD'}",
              flush=True)
    return ok


# ------------------------------------------------------------------ serve


def init_params(model, seed: int, device):
    """Random weights from `seed`, made on `device` inside one jit so each
    float32 draw is cast to the parameter dtype before it reaches memory."""
    return jax.jit(model.init_params,
                   out_shardings=SingleDeviceSharding(device))(
        jax.random.PRNGKey(seed))


def make_requests(vocab: int, seed: int, lens: Sequence[int], n: int,
                  max_new: Tuple[int, int] = MAX_NEW) -> List[Request]:
    rng = np.random.default_rng(seed)
    reqs = [Request(id=i,
                    prompt=rng.integers(0, vocab, lens[i % len(lens)]).tolist(),
                    max_new_tokens=int(rng.integers(*max_new, endpoint=True)))
            for i in range(n)]
    if len({tuple(r.prompt) for r in reqs}) != n:
        raise ValueError("seeded prompts collide; the logit tap keys by prompt")
    return reqs


def warm(engine: ServeEngine, lens: Sequence[int]) -> float:
    """Compile every program serving will run (prefill at each prompt
    length, the cache scatter, the decode step) by serving one request
    per length locally. Returns host wall seconds."""
    t0 = time.perf_counter()
    for i, n in enumerate(lens):
        engine.add_request(Request(id=-1 - i, prompt=[1] * n,
                                   max_new_tokens=2))
    engine.run_until_drained()
    return time.perf_counter() - t0


class LogitTap:
    """Keeps each prompt's prefill logits (by prompt), from the engine's
    own compiled prefill, and each request's first decode-step logits (by
    request id), from the model's decode step on the inputs of the
    engine's step, run just before it (the engine's step returns only
    the next tokens and positions). The served tokens alone cannot be
    held to the reference: with random weights near-ties make argmax
    fragile."""

    def __init__(self, engine: ServeEngine):
        self.prefill: Dict[Tuple[int, ...], np.ndarray] = {}
        self.decode: Dict[int, np.ndarray] = {}
        prefill, decode = engine._prefill_one, engine._decode
        step_logits = jax.jit(lambda params, cache, batch: engine.model
                              .decode_step(params, cache, {
                                  "tokens": batch["tokens"],
                                  "positions": batch["positions"]})[0])

        def prefill_tap(params, tokens):
            logits, cache = prefill(params, tokens)
            self.prefill[tuple(np.asarray(tokens[0]).tolist())] = \
                np.asarray(logits[0, -1], np.float32)
            return logits, cache

        def decode_tap(params, cache, batch):
            fresh = {s: r.id for s, r in enumerate(engine.slot_req)
                     if r is not None and len(r.output) == 1}
            if fresh:               # before the step donates the cache
                logits = step_logits(params, cache, batch)
                for s, rid in fresh.items():
                    self.decode[rid] = np.asarray(logits[s, -1], np.float32)
            return decode(params, cache, batch)

        engine._prefill_one, engine._decode = prefill_tap, decode_tap


def serve(engines: Sequence[ServeEngine], reqs: List[Request]) -> bool:
    """Route `reqs` through a live fleet, one replica per engine; True
    when every request completed with its full decode budget."""
    t0 = time.perf_counter()
    with serve_fleet(engines) as fleet:
        for r in reqs:
            if not fleet.router.submit(r):
                raise RuntimeError(f"request {r.id} shed")
        done = fleet.router.flush(max_ticks=2000)
        stats = dict(fleet.router.stats)
    secs = time.perf_counter() - t0
    complete = sorted(r.id for r in done) == sorted(r.id for r in reqs) \
        and all(len(r.output) == r.max_new_tokens for r in reqs)
    print(f"  served {len(done)}/{len(reqs)} requests over "
          f"{len(engines)} replica(s) in {secs:.1f} s host wall "
          f"(router ticks {stats['ticks']}, shed {stats['shed']})",
          flush=True)
    return complete


def reference_check(params, cfg: ModelConfig, reqs: List[Request],
                    tap: LogitTap, tol: float = LOGIT_TOL) -> bool:
    """Each request's prefill and first decode-step logits against a
    float32 forward of prompt + first token. The forward runs once at
    the longest length: it is causal, so padding after a sequence leaves
    its rows as they are."""
    fwd = jax.jit(functools.partial(dense_logits, cfg=cfg))
    T = max(len(r.prompt) for r in reqs) + 1
    worst = [0.0, 0.0]
    for r in reqs:
        toks = np.zeros((T,), np.int32)
        seq = r.prompt + r.output[:1]
        toks[:len(seq)] = seq
        want = np.asarray(fwd(params, jnp.asarray(toks)))
        n = len(r.prompt)
        errs = (rel_err(tap.prefill[tuple(r.prompt)], want[n - 1]),
                rel_err(tap.decode[r.id], want[n]))
        worst = [max(a, b) for a, b in zip(worst, errs)]
    ok = max(worst) <= tol
    print(f"  logits vs float32 reference over {len(reqs)} requests: "
          f"prefill err {worst[0]:.2e}, decode err {worst[1]:.2e} "
          f"(tol {tol:.0e}) {'ok' if ok else 'BAD'}", flush=True)
    return ok


def serve_phase(cfg: ModelConfig, *, slots: int, max_len: int,
                lens: Sequence[int], n_requests: int, seed: int = SEED,
                tol: float = LOGIT_TOL) -> bool:
    device = jax.devices()[0]
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(model, seed, device))
    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    print(f"  weights {nbytes / 1e9:.2f} GB on {device} "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)
    engine = ServeEngine(model, params, slots, max_len, device=device)
    print(f"  compile + warm (prefill at {list(lens)}, decode): "
          f"{warm(engine, lens):.1f} s host wall", flush=True)
    tap = LogitTap(engine)
    reqs = make_requests(cfg.vocab_size, seed, lens, n_requests)
    complete = serve([engine], reqs)
    del engine                 # frees the cache for the reference forward
    gc.collect()
    matched = reference_check(params, cfg, reqs, tap, tol)
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"  device peak memory {stats['peak_bytes_in_use'] / 1e9:.2f}"
              f" GB of {stats.get('bytes_limit', 0) / 1e9:.2f} GB",
              flush=True)
    return complete and matched


# --------------------------------------------------------------- replicas


def replicas_phase(cfg: ModelConfig, devices: Sequence[Any], *, slots: int,
                   max_len: int, lens: Sequence[int], n_requests: int,
                   seed: int = SEED, tol: float = REPLICA_TOL) -> bool:
    """One replica per device, built and compiled in parallel. The same
    requests go to replica 0 alone and then to the whole fleet; each
    request's first-token logits must agree between the two runs, and
    every replica must have served from its own device."""
    model = build_model(cfg)
    engines: List[Any] = [None] * len(devices)
    errors: List[Exception] = []

    def build(i):
        try:
            params = init_params(model, seed, devices[i])
            engines[i] = ServeEngine(model, params, slots, max_len,
                                     device=devices[i])
            warm(engines[i], lens)
        except Exception as e:  # noqa: BLE001 -- re-raised below
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=build, args=(i,))
               for i in range(len(devices))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    print(f"  {len(engines)} replicas built + compiled in "
          f"{time.perf_counter() - t0:.1f} s host wall", flush=True)
    taps = [LogitTap(e) for e in engines]

    single = make_requests(cfg.vocab_size, seed, lens, n_requests)
    ok = serve(engines[:1], single)
    want = {r.id: taps[0].prefill[tuple(r.prompt)] for r in single}
    for tap in taps:
        tap.prefill.clear()
    before = [e.stats["prefills"] for e in engines]
    fleet = make_requests(cfg.vocab_size, seed, lens, n_requests)
    ok &= serve(engines, fleet)
    got = {}
    for tap in taps:
        got.update(tap.prefill)
    err = max(rel_err(got[tuple(r.prompt)], want[r.id]) for r in fleet)
    same_tokens = sum(a.output == b.output for a, b in zip(single, fleet))
    served = [e.stats["prefills"] - b for e, b in zip(engines, before)]
    placed = [{d.id for leaf in jax.tree.leaves((e.params, e.cache))
               for d in leaf.devices()} for e in engines]
    own = all(p == {e.device.id} for p, e in zip(placed, engines))
    distinct = len({e.device.id for e in engines}) == len(engines)
    print(f"  requests per replica {served} on devices "
          f"{[e.device.id for e in engines]}; weights and cache on their "
          f"own device: {own}", flush=True)
    print(f"  first-token logits fleet vs single replica: err {err:.2e} "
          f"(tol {tol:.0e}); identical outputs {same_tokens}/{len(fleet)}",
          flush=True)
    return bool(ok and err <= tol and own and distinct and min(served) > 0)


# ------------------------------------------------------------------- main


def main(argv: Sequence[str] = ()) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the one-replica-per-chip fleet phase")
    args = ap.parse_args(argv)
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {info['platform']!r})",
              file=sys.stderr)
        return 1
    if info["count"] < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {info['count']} found",
              file=sys.stderr)
        return 1
    print(f"device: {info}; compile cache: {enable_compile_cache()}",
          flush=True)
    cfg = get_config("llama3-8b").replace(n_layers=LAYERS)
    print(f"model: {cfg.name} at published widths, {LAYERS} of 32 layers; "
          f"{SLOTS} slots x max_len {MAX_LEN}; prompts {list(PROMPT_LENS)}; "
          f"{N_REQUESTS} requests; seed {SEED}", flush=True)
    sizes = dict(slots=SLOTS, max_len=MAX_LEN, lens=PROMPT_LENS,
                 n_requests=N_REQUESTS)
    if args.chips == 4:
        ok = phase("replicas", lambda: replicas_phase(
            cfg, jax.devices()[:4], **sizes))
    else:
        cases = lambda: kernel_cases(
            cfg, get_config("phi3.5-moe-42b-a6.6b"),
            get_config("zamba2-2.7b"), SEQ_LEN, SLOTS, MAX_LEN)
        ok = phase("kernels", lambda: kernel_phase(cases(),
                                                   need_compiled=True))
        ok &= phase("serve", lambda: serve_phase(cfg, **sizes))
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
