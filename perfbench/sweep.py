#!/usr/bin/env python3
"""Offered-load sweep of one cell, to find the highest rate it sustains.

    python3 perfbench/sweep.py --workload <cell> --seconds <s> \
        --rates <r>,<r>,... [--seed <n>]

For each rate (requests/s) one run of the cell's mix at that rate. Prints
one JSON line per rate: requests due, those finished inside the window,
the backlog (sent, not finished) at the window's close, the drain time
after it, failures, the p90 latency and the output tokens/s. A rate is
sustained where the backlog at the close stays within what the slots hold
and the drain is short: beyond it the queue grows through the window. The
cell's rate in `traffic/<mix>.json` is set to about 0.8 of the highest
rate sustained.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from perfbench import harness
    harness.enable_cache()
    base = harness.load_cell(args.workload)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = copy.deepcopy(base)
        cell.mix["arrivals"]["rate_per_s"] = rate
        try:
            res, _, win = harness.run_cell(
                cell, args.seed, args.seconds, False,
                t_start=time.perf_counter(), log=log)
        except harness.NoChip as e:
            log(f"perfbench: {e}")
            return 2
        done = [d for d in win.done if d is not None]
        in_window = sum(d <= win.seconds for d in done)
        print(json.dumps({
            "rate": rate, "due": len(win.done), "done_in_window": in_window,
            "backlog_at_close": len(win.done) - in_window,
            "drain_s": max(done) - win.seconds if done else None,
            "failed": win.failed, "correct": res["correct"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
