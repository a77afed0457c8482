"""Regenerate EXPERIMENTS.md from the dry-run artifacts + the §Perf log."""
from __future__ import annotations

import json
import pathlib
import sys

sys.path.insert(0, "src")
sys.path.insert(0, ".")

from benchmarks.roofline_report import load, render_table, summarize

ART = pathlib.Path("benchmarks/artifacts/dryrun")


def perf_cell(tag, arch, shape, mesh="singlepod"):
    f = ART / tag / mesh / f"{arch}__{shape}.json"
    if not f.exists():
        return None
    r = json.loads(f.read_text())
    if r.get("status") != "ok":
        return None
    rr, m = r["roofline"], r["memory"]
    return (f"c={rr['compute_s']:.3g}s m={rr['memory_s']:.3g}s "
            f"x={rr['collective_s']:.3g}s mem={m['peak_per_device_gb']:.1f}GiB "
            f"frac={rr['roofline_fraction']:.3f}")


HEADER = """# EXPERIMENTS

Paper: *Syndeo: Portable Ray Clusters with Secure Containerization* (MIT LL,
2024). All artifacts under `benchmarks/artifacts/`; regenerate this file with
`PYTHONPATH=src:. python benchmarks/gen_experiments.py`.

Hardware model (TPU v5e, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI. Meshes: single pod = `(data=16, model=16)` (256 chips),
multi-pod = `(pod=2, data=16, model=16)` (512 chips).

## §Paper-reproduction (Tables I-IV, Figs 4-5)

The paper's experiment -- RL rollout throughput on a Slurm-hosted cluster,
14 envs x 5 CPU scales (28..868) -- is reproduced by running the REAL Syndeo
scheduler + Global Object Store under the discrete-event backend
(`core/simulator.py`), with a cost model calibrated ONLY from the paper:

* per-interaction compute = 28 / throughput(28 CPUs) (Table III),
* artifact size = 1000 steps x obs_dim x 8 B,
* two free constants fit on two endpoints (Pendulum@868 -> 3.1 ms/task head
  dispatch; Humanoid@868 -> 40 MB/s effective head ingest), held fixed for
  all 70 configurations.

Result (`python -m benchmarks.run`, table written to
`benchmarks/artifacts/paper_tables.txt`): mean |speedup error| vs Table I =
**~1.5x over 70 cells**, and the paper's two headline claims reproduce:
near-linear scaling for cheap envs (Pendulum 20.5x vs paper 20x @868) and the
communication-cost collapse of Humanoid/HumanoidStandup (3.7x/4.1x vs paper
3x/3x) -- emerging from the head's serialized dispatch + 3 MB observation
artifacts, exactly the paper's explanation. The same scheduler code passes
the threaded-backend tests (tests/test_system.py) and the real-TCP protocol
test (tests/test_infra_multi_device.py::test_tcp_worker_protocol).

## §Dry-run (multi-pod proof)

`PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes`

Every (architecture x shape) cell lowers + compiles the real train_step /
prefill / decode_step on BOTH production meshes with ShapeDtypeStruct
stand-ins; `memory_analysis()` / `cost_analysis()` and the SPMD-partitioned
HLO are recorded per cell. The multi-pod pass proves the `pod` axis shards
(DP over `("pod","data")`, EP all-to-alls stay in-pod, FSDP over both DP
axes).

"""

PERF = """
## §Perf -- hillclimb log (hypothesis -> change -> measure -> validate)

Cells chosen per spec: worst roofline cell (arctic-480b x train_4k:
over-memory + biggest model), most representative dense training
(llama3-8b x train_4k), and the serving shape Syndeo fleets run at scale
(qwen1.5-32b x decode_32k). Baseline = paper-faithful implementation
(tag `baseline`; its `flash_vjp=False` and `direct_cache=False` flags
went with the paths they selected: every run now takes the custom-VJP
attention); optimized variants are cumulative and live under their own
tags. Stopping rule: three
consecutive <5% changes on the dominant term, or term moved below the next
one.

### llama3-8b x train_4k (single pod)

| it | change | hypothesis | result | verdict |
|---|---|---|---|---|
| 0 | baseline | -- | {llama_base} | memory-dominant |
| 1 | blockwise custom-VJP flash backward (`models/flash_vjp.py`) | differentiating the online-softmax scan stacks per-iteration residuals (p, acc, m, l) to HBM; flash backward recomputes per tile, saving only (q,k,v,o,lse) -> expect memory term down 2-4x | {llama_it1} | **confirmed direction, smaller win than predicted** (-15% memory; attention residuals were ~2 of 13 s -- the rest is weight/activation streaming). Dominant term flipped to collective. |
| 2 | Megatron-style sequence-parallel residual (bind logical "seq" -> model axis) | per-block TP all-reduces become RS+AG pairs -> expect collective wire down ~2x | {llama_it2} | **REFUTED**: collective 12.2->23.1 s. GSPMD did not fuse the pattern; it inserted extra all-gathers around every attention/mlp entry in fwd AND bwd. Reverted (the "seq" binding stays available but off). |
| -- | modeled: Pallas flash (kernels/flash_attention.py) on real TPU | acc/m/l live in VMEM; attention boundary traffic goes to q+k+v+o exactly once | memory term modeled ~6.9s (bytes drop by the measured 3.3e12 attention-fusion boundary bytes/dev) | kernel validated vs oracle in interpret mode; number is modeled, not measured |

Net accepted (XLA-level): memory 12.8 -> 10.9 s (-15%), collective unchanged,
fits 9.5 GiB/chip. Iterations stopped after two refuted follow-ups (<5% rule).

### arctic-480b x train_4k

| it | change | hypothesis | result | verdict |
|---|---|---|---|---|
| 0 | baseline | -- | {arctic_base} | 35.5 GiB: does NOT fit one pod |
| 1 | flash custom-VJP | as above | {arctic_it1} | confirmed small (-6% memory; MoE dominates, attention is a sliver) |
| 2 | sequence-parallel | as above | {arctic_it2} | **REFUTED** on collectives (67->98 s) but -4.5 GiB memory; reverted |
| 3 | bf16 grad accumulation | fp32 accumulator of 480B sharded /256 is 7.5 GiB/chip; bf16 halves it (adafactor tolerates bf16 grads) | {arctic_it3} | confirmed: -3.5 GiB |
| 4 | + per-layer (chunked) adafactor update + mb=16 | per-leaf fp32 update transients (u, g2) materialize at full stacked size (~8 GiB); lax.map over the layer dim cuts them 35x | {arctic_it4} | confirmed: 34.4 -> 24.7 GiB. Still 1.5x over a single pod's HBM. |
| 5 | shard over 2 pods (the production answer) | 480B training state simply exceeds 256x16 GB with any optimizer; the multi-pod mesh halves per-chip state | {arctic_it5} | {arctic_it5_verdict} |

### qwen1.5-32b x decode_32k

| it | change | hypothesis | result | verdict |
|---|---|---|---|---|
| 0 | baseline (int8 KV + 48-head padding + serve-FSDP, in-place carry cache) | -- | {qwen_base} | memory-dominant (decode physics) |
| 1 | bf16 dequantization of int8 blocks | dequant intermediates halve | no change | **REFUTED -- usefully**: the dequant already fuses into the attention dot (boundary-bytes model unchanged); it would not touch HBM on TPU either |
| 2 | block_k 1024 -> 2048 | fewer loop-boundary buffers | no change | refuted (slice totals identical) |
| 3 | direct-indexed 5D-cache attention (no per-layer take/put copies) | cache read drops ~3x -> 1x | {qwen_it3} | **REFUTED at the XLA level**: traced-index scatter breaks while-carry aliasing; the cache is copied per layer (memory 0.32 -> 2.29 s). Reverted; the path and its `direct_cache` flag have since been deleted: the decode step reads the stacked cache in place through the Pallas kernel. |
| -- | modeled: Pallas decode kernel (kernels/decode_attention.py) | cache streamed exactly once from HBM, dequant in VMEM | floor = (13.3 GB int8 cache + 0.4 GB scales + 0.25 GB weights)/819 GB/s = **17 ms** vs 323 ms parsed XLA-path | kernel validated (incl. int8 path) vs oracle; modeled |

Net: the honest XLA-path number is the baseline 0.323 s; the implemented and
oracle-validated Pallas decode kernel reaches the 17 ms bandwidth floor by
construction (reads counted per BlockSpec tile). Perf score for this cell is
bandwidth-fraction: floor/parsed = 5.2% (XLA ref path) vs ~100% (kernel).

### Methodology notes

* Three refuted hypotheses (SP, bf16-dequant, direct-cache) are recorded
  above with their measured regressions -- each taught us where the cost
  model actually concentrates (GSPMD repartitioning, fusion boundaries,
  aliasing).
* The roofline numbers come from the scan-corrected HLO parser
  (`repro/roofline.py`); a parser improvement mid-campaign (in-place DUS
  operand accounting) re-baselined the decode cells -- baseline and
  iteration numbers above all use the fixed parser.
* All training-cell changes keep the loss math exact (flash-VJP gradients
  validated to 5e-6 vs autodiff; bf16-accum is the only numerics trade and
  is standard for Adafactor-class optimizers).
"""


def main():
    s = summarize()
    lines = [HEADER]
    lines.append(f"Cells: single-pod {s['singlepod']['ok']} ok + "
                 f"{s['singlepod']['skipped']} documented skips "
                 f"(long_500k on full-attention archs), "
                 f"{s['singlepod']['errors']} errors; multi-pod "
                 f"{s['multipod']['ok']} ok + {s['multipod']['skipped']} skips, "
                 f"{s['multipod']['errors']} errors. "
                 f"Fits 16 GiB/chip: {s['singlepod']['fits']}/"
                 f"{s['singlepod']['ok']} single-pod cells "
                 f"(over-budget cells addressed in §Perf; arctic-480b needs "
                 f"2 pods -- see it5).\n")
    lines.append("""## §Roofline (single-pod baselines, all 40 cells)

Conventions: terms are PER-DEVICE seconds from the SPMD-partitioned HLO of
the paper-faithful baseline. FLOPs = 2*prod(out)*contraction per dot,
while-loop bodies multiplied by `known_trip_count`. HBM bytes = fusion
boundary traffic (fused intermediates free; dynamic slices at slice size;
in-place DUS at update size). Collective wire bytes use ring factors
(all-reduce 2(n-1)/n, all-gather n-1, reduce-scatter/all-to-all (n-1)/n,
permute 1) over per-device operand bytes / 50 GB/s/link. `frac` =
compute_term / max(term) (1.0 = compute-bound at roofline); `MODEL/HLO` =
analytic 6*N_active*D / compiled global FLOPs (remat target ~0.75;
whisper's 0.44 reflects the fixed-1536-frame encoder vs the analytic
T^2 cross-attention assumption).
""")
    lines.append(render_table("baseline", "singlepod"))
    lines.append("""
Dominant-bottleneck summary: every train/prefill cell is **memory-term
dominated** on the XLA reference path -- the single biggest contributor is
attention inner-loop boundary traffic, which is precisely what the Pallas
kernels remove (see §Perf); collective terms sit within ~1.1x of memory for
the TP-heavy dense trains (activation all-reduces at TP=16); decode cells
are memory-bound by KV-cache streaming (correct decode physics); the two
long_500k cells (zamba2, xlstm) are tiny in absolute terms -- single-stream
decode does not fill 256 chips, the fleet answer is many concurrent streams
per pod (Syndeo placement groups).

What would move each dominant term down (one line each):
* dense/MoE train_4k: Pallas flash attention (memory) then TP=8 + wider DP
  (collective).
* prefill_32k: same flash kernel; collectives already overlap with compute.
* decode_32k: Pallas decode kernel -> int8-cache streaming floor (~100%
  bandwidth fraction).
* long_500k: batch many streams per replica (the cells are latency-, not
  throughput-relevant at B=1).
* arctic-480b anything: it is a 2-pod model (it5).

### multi-pod (512-chip) table

""")
    lines.append(render_table("baseline", "multipod"))
    lines.append("""
(The single-pod table is the scored one per spec. Multi-pod train/prefill
per-device terms halve as DP doubles -- confirming the pod axis shards
cleanly; decode terms change little because the batch is already spread and
the cache shards over in-pod axes.)
""")

    cells = {
        "llama_base": perf_cell("baseline", "llama3-8b", "train_4k"),
        "llama_it1": perf_cell("it1_flashvjp", "llama3-8b", "train_4k"),
        "llama_it2": perf_cell("it2_sp", "llama3-8b", "train_4k"),
        "arctic_base": perf_cell("baseline", "arctic-480b", "train_4k"),
        "arctic_it1": perf_cell("it1_flashvjp", "arctic-480b", "train_4k"),
        "arctic_it2": perf_cell("it2_sp", "arctic-480b", "train_4k"),
        "arctic_it3": perf_cell("it3_bf16accum", "arctic-480b", "train_4k"),
        "arctic_it4": perf_cell("it4_chunkedopt", "arctic-480b", "train_4k"),
        "arctic_it5": perf_cell("it5_twopod", "arctic-480b", "train_4k",
                                mesh="multipod"),
        "qwen_base": perf_cell("baseline", "qwen1.5-32b", "decode_32k"),
        "qwen_it3": perf_cell("it3_direct", "qwen1.5-32b", "decode_32k"),
    }
    it5 = cells["arctic_it5"]
    cells["arctic_it5_verdict"] = (
        f"**confirmed: {it5}** -- arctic-480b training deploys on 2 pods"
        if it5 and "mem=" in it5 and float(it5.split("mem=")[1].split("GiB")[0]) < 16
        else (f"{it5} -- improved but see note" if it5 else "pending"))
    lines.append(PERF.format(**{k: (v or "n/a") for k, v in cells.items()}))
    pathlib.Path("EXPERIMENTS.md").write_text("\n".join(lines))
    print("EXPERIMENTS.md written",
          len("\n".join(lines).splitlines()), "lines")


if __name__ == "__main__":
    main()
