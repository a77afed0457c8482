"""Serving example: a continuous-batching engine on a small LM, served as
a replica actor behind the router -- the same wiring `chip_smoke.py`
drives at full width on a TPU:

    Router -> ActorReplicaHandle -> head (actor_call) -> worker
           -> ReplicaActor -> ServeEngine

    PYTHONPATH=src python examples/serve.py
"""
import time

import jax

from repro.configs import get_config
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.fleet import serve_fleet


def main():
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, batch_slots=4, max_len=64)

    prompts = [[1, 5, 9], [2, 4], [7, 7, 7, 7], [3], [8, 1, 2], [9, 9]]
    reqs = [Request(id=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    t0 = time.time()
    with serve_fleet([engine]) as fleet:
        for r in reqs:
            assert fleet.router.submit(r)
        fleet.router.flush()
        p99_ms = fleet.router.p99_ms()
    dt = time.time() - t0

    for r in reqs:
        print(f"req {r.id}: prompt={r.prompt} -> {r.output}")
    s = engine.stats
    print(f"\n{s['completed']} requests, {s['decoded_tokens']} tokens in "
          f"{dt:.2f}s host wall on {engine.device} ({s['ticks']} engine "
          f"ticks, {s['prefills']} prefills, router p99 {p99_ms:.0f} ms)")
    assert all(r.done for r in reqs)


if __name__ == "__main__":
    main()
