"""§Perf hillclimb driver: run tagged dry-run variants of the three chosen
cells and print before/after roofline terms.

Cells (chosen per spec: worst roofline fraction / most collective-bound /
most representative):
  * arctic-480b  x train_4k   -- worst cell (over-memory, biggest model)
  * llama3-8b    x train_4k   -- representative dense training
  * qwen1.5-32b  x decode_32k -- serving cell (Syndeo's fleet workload)

Each iteration is cumulative (it2 includes it1, ...). The paper-faithful
baseline lives under tag "baseline" and is never overwritten.
"""
from __future__ import annotations

import json
import sys

sys.path.insert(0, "src")

ITERATIONS = [
    # (arch, shape, tag, overrides)
    ("llama3-8b", "train_4k", "it1_flashvjp", {}),
    ("llama3-8b", "train_4k", "it2_sp", {"rules": {"seq": ("model",)}}),
    ("arctic-480b", "train_4k", "it1_flashvjp", {}),
    ("arctic-480b", "train_4k", "it2_sp", {"rules": {"seq": ("model",)}}),
    ("arctic-480b", "train_4k", "it3_bf16accum",
     {"rules": {"seq": ("model",)}, "accum_dtype": "bfloat16"}),
]


def main():
    from repro.launch.dryrun import run_cell
    only = sys.argv[1] if len(sys.argv) > 1 else None
    for arch, shape, tag, ov in ITERATIONS:
        if only and only not in (arch, tag):
            continue
        rec = run_cell(arch, shape, multi_pod=False, force=True,
                       overrides=ov, tag=tag)
        if rec["status"] == "ok":
            r = rec["roofline"]
            print(f"  -> {tag}: c={r['compute_s']:.3e} m={r['memory_s']:.3e} "
                  f"x={r['collective_s']:.3e} frac={r['roofline_fraction']:.3f} "
                  f"mem={rec['memory']['peak_per_device_gb']:.1f}GiB")


if __name__ == "__main__":
    main()
