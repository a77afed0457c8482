"""Serving-plane benchmark: replica goodput scaling, weight broadcast,
and the live actor path.

The serving plane composes two batching layers -- slot-level continuous
batching inside each `ServeEngine` replica and token-level admission
across replicas in `serve/router.py` -- on top of the p2p data plane's
broadcast trees for weight distribution. This benchmark measures that
stack on the REAL Router/StubEngine/ObjectStore code:

1. *Goodput vs replica count*: an open-loop arrival stream at a fixed
   per-replica rate (so N replicas face N x the single-replica load)
   driven through `SimCluster.run_serve`. Reported per replica count:
   goodput (completed requests per virtual second), p99 end-to-end
   latency over the router's sliding window, and the head-link payload
   bytes (must stay 0 -- weights and results ride the worker NICs).
   The smoke gate: 4 replicas sustain >= 3x the single-replica goodput
   with BOTH arms inside the same p99 budget -- continuous batching
   across replicas must scale throughput without giving back the tail.

2. *Weight distribution*: a fat weights object broadcast to the replica
   fleet through the binomial tree (zero head payload bytes), then a
   scale-up replica placed on a bare worker -- its nearest-fresh fetch
   must come from a peer replica, never the head.

3. *Actor path* (real sockets): a worker-hosted `ReplicaActor` driven
   through actor_create/actor_call/actor_result/actor_exit with an
   `ActorReplicaHandle` + `Router` on top; routed outputs must match the
   engine run locally, and the router's `stats_sink` must surface the
   serving gauges (syndeo_serve_requests / shed / p99_ms and
   syndeo_replica_count) through the head's `metrics` op.

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--quick]
      PYTHONPATH=src python benchmarks/serve_bench.py --serve-smoke
"""
from __future__ import annotations

import argparse
from typing import Dict, List

from repro.core import SimCluster, SimCostModel
from repro.serve.engine import Request, StubEngine
from repro.serve.fleet import serve_fleet
from repro.serve.router import Router

MB = 1_000_000


# ------------------------------------------- goodput vs replica count


def serve_run(n_replicas: int, rate_rps: float, n_requests: int,
              tokens: int = 8, batch_slots: int = 4,
              weight_bytes: int = 8 * MB,
              tick_every: float = 0.01) -> Dict[str, float]:
    """One open-loop serving run: `n_requests` arrive evenly spaced at
    `rate_rps`, routed over `n_replicas` replica actors (one per sim
    worker, weights fetched p2p from the first worker's copy). Every
    request must complete with the engine-deterministic output."""
    cost = SimCostModel(task_time_s=lambda s: 0.05, jitter=0.0,
                        data_plane="p2p", result_location="worker")
    sim = SimCluster(cost)
    workers = sim.add_workers(n_replicas)
    weights = sim.store.put(workers[0], bytearray(weight_bytes))
    head0 = sim.store.stats["head_relayed_bytes"]
    router = Router(clock=lambda: sim.now)
    for i in range(n_replicas):
        handle = sim.add_replica(f"r{i}", batch_slots=batch_slots,
                                 weights=weights)
        assert handle is not None, f"replica r{i} did not place"
        router.add_replica(f"r{i}", handle)
    arrivals = [(i / rate_rps,
                 Request(id=i, prompt=[i, 17], max_new_tokens=tokens))
                for i in range(n_requests)]
    t0 = sim.now
    completed = sim.run_serve(router, arrivals, tick_every=tick_every)
    makespan = max(sim.now - t0, 1e-9)
    wrong = [q.id for q in completed
             if q.output != StubEngine.stub_output(q.prompt,
                                                   q.max_new_tokens)]
    assert not wrong, f"routed outputs diverged for requests {wrong}"
    return {"replicas": float(n_replicas),
            "rate_rps": rate_rps,
            "completed": float(len(completed)),
            "expected": float(n_requests),
            "goodput_rps": len(completed) / makespan,
            "p99_ms": router.p99_ms(),
            "makespan_s": makespan,
            "head_relayed_bytes": float(
                sim.store.stats["head_relayed_bytes"] - head0)}


def bench_serve(replica_counts: List[int], rate_per_replica: float = 40.0,
                requests_per_replica: int = 120) -> List[Dict[str, float]]:
    return [serve_run(n, rate_rps=rate_per_replica * n,
                      n_requests=requests_per_replica * n)
            for n in replica_counts]


def print_serve(rows: List[Dict[str, float]]):
    print("\n== serving plane: goodput + p99 vs replica count "
          "(per-replica load held constant) ==")
    print(f"{'replicas':>8} {'rate r/s':>9} {'goodput r/s':>12} "
          f"{'p99 ms':>8} {'scaling':>8} {'head MB':>8}")
    base = rows[0]["goodput_rps"] if rows else 1.0
    for r in rows:
        print(f"{r['replicas']:>8.0f} {r['rate_rps']:>9.0f} "
              f"{r['goodput_rps']:>12.1f} {r['p99_ms']:>8.1f} "
              f"{r['goodput_rps'] / max(base, 1e-9):>7.1f}x "
              f"{r['head_relayed_bytes'] / MB:>8.1f}")


# ------------------------------------------------- weight distribution


def weights_run(n_replicas: int = 4,
                obj_bytes: int = 8 * MB) -> Dict[str, float]:
    """Broadcast the weights object to the replica fleet through the
    binomial tree, then scale up one replica on a deliberately bare
    worker: its weights must arrive by a nearest-fresh peer fetch, with
    the head's NIC serving zero payload bytes throughout."""
    sim = SimCluster(SimCostModel(jitter=0.0, data_plane="p2p",
                                  result_location="worker"))
    ids = sim.add_workers(n_replicas + 2)
    weights = sim.store.put(ids[0], bytearray(obj_bytes))
    head0 = sim.store.stats["head_relayed_bytes"]
    makespan = sim.broadcast_object(weights, ids[1:n_replicas + 1],
                                    mode="tree")
    # fill every pre-warmed worker with a replica so the late joiner
    # can only land on the one bare worker (ids[-1]) and MUST fetch
    for i in range(n_replicas + 1):
        assert sim.add_replica(f"r{i}", weights=weights) is not None
    late = sim.add_replica("r-late", weights=weights)
    assert late is not None, "scale-up replica did not place"
    fetched = late.worker_id in sim.store.locations(weights)
    return {"consumers": float(n_replicas),
            "broadcast_s": makespan,
            "rounds": float(sim.store.stats["broadcast_rounds"]),
            "tree_edges": float(sim.store.stats["tree_edges"]),
            "head_relayed_bytes": float(
                sim.store.stats["head_relayed_bytes"] - head0),
            "scale_up_fetched": float(fetched),
            "scale_up_versioned": float(
                late.weights_version == weights.id)}


def print_weights(wr: Dict[str, float]):
    print("\n== weight distribution: broadcast tree + scale-up fetch ==")
    print(f"  consumers          : {wr['consumers']:.0f}")
    print(f"  broadcast makespan : {wr['broadcast_s']:.4f} s "
          f"({wr['rounds']:.0f} rounds, {wr['tree_edges']:.0f} edges)")
    print(f"  head payload bytes : {wr['head_relayed_bytes']:.0f}")
    print(f"  scale-up fetch     : "
          f"{'peer copy' if wr['scale_up_fetched'] else 'MISSING'}, "
          f"version "
          f"{'pinned' if wr['scale_up_versioned'] else 'UNPINNED'}")


# ------------------------------------------------- actor path (sockets)


def actor_run(n_requests: int = 3, tokens: int = 4) -> Dict[str, float]:
    """Real sockets: one worker-hosted ReplicaActor behind the router,
    with the router's stats_sink feeding the head's serve gauges."""
    with serve_fleet([StubEngine(2)], call_timeout_s=10.0) as fleet:
        reqs = [Request(id=i, prompt=[i, 17], max_new_tokens=tokens)
                for i in range(n_requests)]
        for q in reqs:
            assert fleet.router.submit(q)
        done = fleet.router.flush(max_ticks=200)
        outputs_ok = (
            sorted(q.id for q in done) == sorted(q.id for q in reqs)
            and all(q.output == StubEngine.stub_output(
                q.prompt, q.max_new_tokens) for q in reqs))
        gauges = fleet.server.dispatch({"op": "metrics"})
    return {"completed": float(len(done)),
            "outputs_ok": float(outputs_ok),
            "gauge_requests": float(gauges.get("syndeo_serve_requests", -1)),
            "gauge_shed": float(gauges.get("syndeo_serve_shed", -1)),
            "gauge_p99_ms": float(gauges.get("syndeo_serve_p99_ms", -1.0)),
            "gauge_replicas": float(gauges.get("syndeo_replica_count", -1))}


def print_actor(ar: Dict[str, float]):
    print("\n== actor path (real sockets): routed replica + serve gauges ==")
    print(f"  routed requests    : {ar['completed']:.0f} "
          f"({'outputs match engine' if ar['outputs_ok'] else 'DIVERGED'})")
    print(f"  gauges             : requests={ar['gauge_requests']:.0f} "
          f"shed={ar['gauge_shed']:.0f} p99={ar['gauge_p99_ms']:.1f}ms "
          f"replicas={ar['gauge_replicas']:.0f}")


# --------------------------------------------------------------- smoke


def serve_smoke() -> int:
    """CI gate: 4 replicas sustain >= 3x single-replica goodput at an
    equal p99 budget with every request completed; weight broadcast and
    scale-up fetch put ZERO payload bytes on the head's link; and the
    real-socket actor path routes correctly while exporting the serving
    gauges through the head's metrics op."""
    p99_budget_ms = 300.0
    one = serve_run(1, rate_rps=40.0, n_requests=120)
    four = serve_run(4, rate_rps=160.0, n_requests=480)
    print_serve([one, four])
    wr = weights_run()
    print_weights(wr)
    ar = actor_run()
    print_actor(ar)
    ok = True
    for r in (one, four):
        if r["completed"] != r["expected"]:
            print(f"FAIL: {r['replicas']:.0f}-replica run dropped "
                  f"{r['expected'] - r['completed']:.0f} requests")
            ok = False
        if r["p99_ms"] > p99_budget_ms:
            print(f"FAIL: {r['replicas']:.0f}-replica p99 "
                  f"{r['p99_ms']:.1f} ms over the {p99_budget_ms:.0f} ms "
                  f"budget")
            ok = False
        if r["head_relayed_bytes"] != 0:
            print(f"FAIL: serving run relayed "
                  f"{r['head_relayed_bytes']:.0f} payload bytes through "
                  f"the head")
            ok = False
    ratio = four["goodput_rps"] / max(one["goodput_rps"], 1e-9)
    if ratio < 3.0:
        print(f"FAIL: 4-replica goodput only {ratio:.2f}x single-replica "
              f"(need >= 3x at equal p99 budget)")
        ok = False
    if wr["head_relayed_bytes"] != 0:
        print(f"FAIL: weight broadcast put {wr['head_relayed_bytes']:.0f} "
              f"payload bytes on the head's link")
        ok = False
    if not (wr["scale_up_fetched"] and wr["scale_up_versioned"]):
        print("FAIL: scale-up replica missing its nearest-fresh weight "
              "copy or version pin")
        ok = False
    if not ar["outputs_ok"]:
        print("FAIL: socket-routed outputs diverged from the local engine")
        ok = False
    if ar["gauge_requests"] != ar["completed"] or ar["gauge_shed"] != 0:
        print(f"FAIL: serve gauges off (requests "
              f"{ar['gauge_requests']:.0f} != {ar['completed']:.0f} or "
              f"shed {ar['gauge_shed']:.0f} != 0)")
        ok = False
    if ar["gauge_replicas"] != 1 or ar["gauge_p99_ms"] <= 0:
        print(f"FAIL: replica_count {ar['gauge_replicas']:.0f} or p99 "
              f"gauge {ar['gauge_p99_ms']:.1f} not exported")
        ok = False
    print("\nserve smoke:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--serve-smoke", action="store_true")
    args = ap.parse_args()
    if args.serve_smoke:
        raise SystemExit(serve_smoke())
    counts = [1, 2, 4] if args.quick else [1, 2, 4, 8]
    print_serve(bench_serve(counts))
    print_weights(weights_run())
    print_actor(actor_run())


if __name__ == "__main__":
    main()
