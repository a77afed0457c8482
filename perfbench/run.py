#!/usr/bin/env python3
"""On-chip benchmark of the served path: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs from the root of a checkout on a machine that holds the chips the
cell asks for (`BENCHMARK.json`). With `--trace 0` the result holds the
cell's end-to-end metrics, with `--trace 1` its per-layer metrics, read from
a profiler trace of the window. The last line of stdout is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

and the last lines of stderr are the numbers compared, each beside its
limit. Exits nonzero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for. JAX's compile cache lives in `<checkout>/.jax_cache`,
so only the first run of a cell in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from perfbench import harness
    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        result, checks, _ = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"perfbench: {e}")
        return 2
    for name, (value, limit) in checks.items():
        log(f"check {name} {value!r} limit {limit!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
