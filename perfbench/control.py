#!/usr/bin/env python3
"""The comparison's control, and the program's readings beside it, on the
chip at a cell's own size.

    python3 perfbench/control.py --workload <cell> --seconds <s> \
        --seeds <n>,<n>,...

For each seed, one run of the cell (a short window at the cell's own load)
whose sample of served requests is also put through the reference at fp8
(the family's `logits_at(quant="fp8")`): the gap of the token that fp8
puts first, read under the float32 reference at the same positions. Prints one JSON line per seed
with the program's numbers and verdict and the control's, the control's
verdict taken by the same comparison (`check.verdict`) against the limits
in `limits/<cell>.json`, which are set from these readings. Exits 1 where
the control comes out correct on any seed. The benchmark's own runs never
run the control.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from perfbench import check, harness
    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    caught = True
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            res, _, _ = harness.run_cell(
                cell, seed, args.seconds, False, t_start=time.perf_counter(),
                control="fp8", log=log)
        except harness.NoChip as e:
            log(f"perfbench: {e}")
            return 2
        control_correct = check.verdict(res["control"], cell.limits)
        caught = caught and not control_correct
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "program": {k: v["value"]
                                      for k, v in res["checks"].items()},
                          "control_correct": control_correct,
                          "control": res["control"],
                          "failed": res["failed"]}), flush=True)
    if not caught:
        log("control: the control came out correct on some seed")
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
