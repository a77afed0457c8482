import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) cell this lowers + compiles the real
train_step / serve_step against ShapeDtypeStruct stand-ins on the production
mesh (single-pod 16x16 = 256 chips, multi-pod 2x16x16 = 512 chips), prints
memory_analysis() (fits/doesn't fit) and cost_analysis(), and extracts the
scan-corrected roofline terms (repro.roofline). Results are written as JSON
artifacts under benchmarks/artifacts/dryrun/ -- EXPERIMENTS.md reads them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ModelConfig, active_param_count, param_count
from repro.configs.shapes import ShapeConfig, applicable
from repro.launch.mesh import dp_degree, make_production_mesh
from repro.models import build_model, cache_specs, input_specs, shape_window
from repro.models.registry import make_batch
from repro.optim.optimizers import make_optimizer, warmup_cosine
from repro.roofline import (HloCostModel, dominant_term, model_flops,
                            roofline_fraction, roofline_terms)
from repro.sharding import axes as AX
from repro.sharding.rules import named_shardings, param_pspecs, zero1_extend
from repro.train.steps import make_init_state, make_train_step

ART_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"

# per-arch microbatch counts for train_4k (memory-driven; see DESIGN.md)
MICROBATCH = {
    "internvl2-76b": 16,
    "arctic-480b": 8,
    "qwen1.5-32b": 8,
    "stablelm-12b": 8,
    "granite-8b": 8,
    "llama3-8b": 8,
    "phi3.5-moe-42b-a6.6b": 8,
    "zamba2-2.7b": 4,
    "whisper-tiny": 2,
    "xlstm-350m": 2,
}


def _sds(x, sharding=None):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _serve_cfg(cfg: ModelConfig) -> ModelConfig:
    """Serving shards weights over the DP axes too when the model-parallel
    shard alone would still be large (>2 GiB/chip): memory-bound decode
    streams weights anyway, so gathering them over ICI is the right trade."""
    if param_count(cfg) * 2 / 16 > 2 * 2**30:
        return cfg.replace(fsdp=True)
    return cfg


def _tree_sds(tree, shardings):
    return jax.tree.map(lambda t, s: _sds(t, s), tree, shardings)


def _leaf_sharding(path, leaf, cfg: ModelConfig, mesh, rules, dp_axes,
                   zero1: bool):
    from repro.sharding.axes import _guard_divisibility
    from repro.sharding.rules import logical_spec

    eff = dict(rules)
    if not cfg.fsdp:
        eff["fsdp"] = ()
    spec_logical = logical_spec(path, leaf, cfg)
    out = []
    for ax in spec_logical:
        if ax is None:
            out.append(None)
        else:
            phys = eff.get(ax, ())
            out.append(phys if phys else None)
    spec = _guard_divisibility(mesh, leaf.shape, P(*out))
    if zero1:
        spec = zero1_extend(spec, leaf.shape, mesh, dp_axes)
        spec = _guard_divisibility(mesh, leaf.shape, spec)
    return NamedSharding(mesh, spec)


def state_shardings(state_shapes, cfg: ModelConfig, mesh, rules,
                    dp_axes) -> Any:
    """NamedShardings for {"params", "opt", "step"} (ZeRO-1 on opt state)."""
    params_sh = jax.tree_util.tree_map_with_path(
        lambda p, l: _leaf_sharding(p, l, cfg, mesh, rules, dp_axes, False),
        state_shapes["params"])
    opt_sh = jax.tree_util.tree_map_with_path(
        lambda p, l: _leaf_sharding(p, l, cfg, mesh, rules, dp_axes, True),
        state_shapes["opt"])
    return {"params": params_sh, "opt": opt_sh,
            "step": NamedSharding(mesh, P())}


def grad_shardings(params_shapes, cfg: ModelConfig, mesh, rules, dp_axes):
    """ZeRO-2: fp32 grad accumulators take ZeRO-1-extended param shardings."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _leaf_sharding(p, l, cfg, mesh, rules, dp_axes, True),
        params_shapes)


def cache_shardings(cshapes, cfg: ModelConfig, mesh, rules, global_batch: int):
    """KV caches: (layers, batch, seq, cache_kv_heads * hd), int8 scales
    (layers, batch, cache_kv_heads, seq) -> shard batch over the DP axes and
    the *heads* over model (KV replication/padding in the configs guarantees
    divisibility). Recurrent states: shard batch only."""
    from repro.sharding.axes import _guard_divisibility
    batch_axes = rules.get("batch", ())
    model_axes = rules.get("model", ())
    head_dims = {cfg.cache_kv_heads, cfg.eff_kv_heads,
                 cfg.cache_kv_heads * cfg.resolved_head_dim}

    def per_leaf(path, leaf):
        spec = [None] * len(leaf.shape)
        used_batch = used_model = False
        for i, dim in enumerate(leaf.shape):
            if i == 0 and len(leaf.shape) >= 4:
                continue  # stacked-layer dim stays unsharded
            if not used_batch and dim == global_batch:
                spec[i] = batch_axes
                used_batch = True
            elif (not used_model and used_batch and dim in head_dims
                  and i >= len(leaf.shape) - 2):
                spec[i] = model_axes
                used_model = True
        pspec = _guard_divisibility(mesh, leaf.shape, P(*spec))
        return NamedSharding(mesh, pspec)

    return jax.tree_util.tree_map_with_path(per_leaf, cshapes)


def batch_shardings(bspecs, mesh, rules):
    from repro.sharding.axes import _guard_divisibility
    batch_axes = rules.get("batch", ())

    def per_leaf(leaf):
        spec = [batch_axes] + [None] * (len(leaf.shape) - 1)
        pspec = _guard_divisibility(mesh, leaf.shape, P(*spec))
        return NamedSharding(mesh, pspec)

    return jax.tree.map(per_leaf, bspecs)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: Optional[Dict[str, Any]] = None):
    """Lower + compile one cell; returns (lowered, compiled, meta)."""
    overrides = overrides or {}
    cfg = get_config(arch)
    for k, v in overrides.get("cfg", {}).items():
        cfg = cfg.replace(**{k: v})
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = AX.multi_pod_rules() if multi_pod else AX.single_pod_rules()
    rules.update(overrides.get("rules", {}))
    dp_axes = rules["batch"]
    n_groups = dp_degree(mesh)
    window = shape_window(cfg, shape)
    model = build_model(cfg, n_groups=n_groups, window=window)
    bspecs = input_specs(cfg, shape)

    with AX.axis_rules(mesh, rules):
        if shape.kind == "train":
            opt = make_optimizer(cfg.optimizer)
            mb = overrides.get("microbatches", MICROBATCH.get(arch, 4))
            # each microbatch must still cover every DP shard (>=1 seq/shard)
            mb = max(1, min(mb, shape.global_batch // n_groups))
            lr_fn = warmup_cosine(3e-4, 2000, 100000)
            state_shapes = jax.eval_shape(
                make_init_state(model, opt), jax.random.PRNGKey(0))
            st_sh = state_shardings(state_shapes, cfg, mesh, rules, dp_axes)
            # ZeRO-2: fp32 grad accumulator sharded over the DP axes
            g_sh = None if overrides.get("no_zero2") else grad_shardings(
                state_shapes["params"], cfg, mesh, rules, dp_axes)
            step_fn = make_train_step(
                model, opt, lr_fn, n_microbatches=mb, grad_shardings=g_sh,
                accum_dtype=overrides.get("accum_dtype", "float32"))
            b_sh = batch_shardings(bspecs, mesh, rules)
            args = (_tree_sds(state_shapes, st_sh),
                    jax.tree.map(lambda s, sh: _sds(s, sh), bspecs, b_sh))
            metric_sh = NamedSharding(mesh, P())
            out_sh = (st_sh, {"loss": metric_sh, "grad_norm": metric_sh,
                              "lr": metric_sh})
            lowered = jax.jit(step_fn, donate_argnums=(0,),
                              out_shardings=out_sh).lower(*args)
        elif shape.kind == "prefill":
            params_shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
            p_sh = named_shardings(params_shapes, _serve_cfg(cfg), mesh, rules)
            b_sh = batch_shardings(bspecs, mesh, rules)

            def prefill(params, batch):
                return model.prefill(params, batch)
            args = (_tree_sds(params_shapes, p_sh),
                    jax.tree.map(lambda s, sh: _sds(s, sh), bspecs, b_sh))
            # shard the emitted KV cache like the decode cells consume it
            out_shapes = jax.eval_shape(prefill, *args)
            logits_sh = NamedSharding(mesh, P())
            pc_sh = cache_shardings(out_shapes[1], cfg, mesh, rules,
                                    shape.global_batch)
            lowered = jax.jit(prefill,
                              out_shardings=(logits_sh, pc_sh)).lower(*args)
        else:  # decode
            params_shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
            p_sh = named_shardings(params_shapes, _serve_cfg(cfg), mesh, rules)
            cshapes = cache_specs(cfg, shape, window=window)
            c_sh = cache_shardings(cshapes, cfg, mesh, rules, shape.global_batch)
            b_sh = batch_shardings(bspecs, mesh, rules)

            def decode(params, cache, batch):
                return model.decode_step(params, cache, batch)
            args = (_tree_sds(params_shapes, p_sh),
                    _tree_sds(cshapes, c_sh),
                    jax.tree.map(lambda s, sh: _sds(s, sh), bspecs, b_sh))
            logits_sh = NamedSharding(mesh, P())  # (B,1,V) is tiny; B may be 1
            lowered = jax.jit(decode, donate_argnums=(1,),
                              out_shardings=(logits_sh, c_sh)).lower(*args)

    t0 = time.time()
    compiled = lowered.compile()
    meta = {"compile_s": time.time() - t0, "mesh": "2x16x16" if multi_pod else "16x16",
            "n_devices": 512 if multi_pod else 256}
    return lowered, compiled, meta


def analyze(compiled, cfg: ModelConfig, shape: ShapeConfig, n_devices: int) -> Dict[str, Any]:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    cm = HloCostModel(compiled.as_text())
    cost = cm.entry_cost()
    terms = roofline_terms(cost)
    mf = model_flops(cfg, shape)
    hlo_global = cost.flops * n_devices
    mem_gb = (ma.argument_size_in_bytes + ma.output_size_in_bytes
              + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30
    return {
        "memory": {
            "argument_gb": ma.argument_size_in_bytes / 2**30,
            "output_gb": ma.output_size_in_bytes / 2**30,
            "temp_gb": ma.temp_size_in_bytes / 2**30,
            "alias_gb": ma.alias_size_in_bytes / 2**30,
            "peak_per_device_gb": mem_gb,
            "fits_16gb": bool(mem_gb < 16.0),
        },
        "cost_analysis": {"flops_raw": ca.get("flops"),
                          "bytes_raw": ca.get("bytes accessed")},
        "roofline": {
            **terms,
            "dominant": dominant_term(terms),
            "roofline_fraction": roofline_fraction(terms),
            "model_flops_global": mf,
            "hlo_flops_global": hlo_global,
            "useful_ratio": mf / hlo_global if hlo_global else 0.0,
            "collectives": cost.collectives,
        },
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, force: bool = False,
             overrides: Optional[Dict[str, Any]] = None,
             tag: str = "baseline") -> Dict[str, Any]:
    mesh_name = "multipod" if multi_pod else "singlepod"
    out_dir = ART_DIR / tag / mesh_name
    out_dir.mkdir(parents=True, exist_ok=True)
    out_file = out_dir / f"{arch}__{shape_name}.json"
    if out_file.exists() and not force:
        return json.loads(out_file.read_text())

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "params": param_count(cfg), "active_params": active_param_count(cfg),
    }
    if not applicable(cfg.family, cfg.sub_quadratic, shape_name):
        record["status"] = "skipped"
        record["reason"] = ("long_500k requires sub-quadratic attention; "
                            f"{arch} is full-attention (DESIGN.md)")
        out_file.write_text(json.dumps(record, indent=1))
        print(f"SKIP {arch} x {shape_name}: {record['reason']}")
        return record
    try:
        t0 = time.time()
        lowered, compiled, meta = lower_cell(arch, shape_name, multi_pod,
                                             overrides)
        record.update(meta)
        record.update(analyze(compiled, cfg, shape,
                              n_devices=meta["n_devices"]))
        record["status"] = "ok"
        record["total_s"] = time.time() - t0
        r = record["roofline"]
        print(f"OK   {arch} x {shape_name} [{mesh_name}] "
              f"compile={meta['compile_s']:.1f}s "
              f"mem={record['memory']['peak_per_device_gb']:.2f}GiB "
              f"terms(c/m/x)={r['compute_s']:.3e}/{r['memory_s']:.3e}/"
              f"{r['collective_s']:.3e}s dom={r['dominant']} "
              f"frac={r['roofline_fraction']:.3f}")
    except Exception as e:
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()
        print(f"FAIL {arch} x {shape_name} [{mesh_name}]: {record['error']}")
    out_file.write_text(json.dumps(record, indent=1))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS) + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_ok = n_fail = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, mp, force=args.force, tag=args.tag)
                if rec["status"] == "error":
                    n_fail += 1
                else:
                    n_ok += 1
    print(f"\ndone: {n_ok} ok/skip, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
