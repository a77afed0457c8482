#!/usr/bin/env python3
"""Record the small trace that the reducer's test reads.

    python3 perfbench/record_trace.py <out_dir>

Inside a `bench.window` span it runs a matmul program five times in an
`engine.decode` span, sleeps in a `client.idle` span, then runs an add
program three times in a `router.tick` span, each call waited on. It writes
the profiler trace under `<out_dir>` and, beside it, `expect.json` with
what the host clock saw, for the test to hold the reduction to.
"""
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

N = 4096
SLEEP_S = 0.05


def bench_matmul(x):
    return x @ x


def bench_add(x):
    return x + 1


def main(out: Path) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record_trace: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    mm, add = jax.jit(bench_matmul), jax.jit(bench_add)
    x = jax.random.normal(jax.random.key(0), (N, N), jnp.bfloat16)
    mm(x).block_until_ready()
    add(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("engine.decode"):
            for _ in range(5):
                mm(x).block_until_ready()
        with jax.profiler.TraceAnnotation("client.idle"):
            time.sleep(SLEEP_S)
        with jax.profiler.TraceAnnotation("router.tick"):
            for _ in range(3):
                add(x).block_until_ready()
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    (out / "expect.json").write_text(json.dumps({
        "kind": dev.device_kind, "window_host_s": window,
        "sleep_s": SLEEP_S, "programs": {"bench_matmul": 5, "bench_add": 3},
        "matmul_flops": 2 * N ** 3}))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
