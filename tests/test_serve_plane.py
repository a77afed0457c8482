"""Serving plane: replica actors, the continuous-batching router, weight
broadcast, SLO autoscaling -- and the chaos scenarios that must end with
the global storage invariants intact (see tests/README.md, "Service actor
protocol"):

  * replica death mid-decode: its in-flight requests are re-routed, not
    lost, and re-decode to identical outputs (the engine is deterministic
    per prompt),
  * router death: replicas quiesce (finish what the dead router admitted)
    and re-register with a fresh router,
  * weight broadcast during scale-up: a replica joining mid-broadcast
    pulls from the nearest fresh replica; zero payload bytes cross the
    head link either way,
  * drain with in-flight requests: a retired replica finishes every
    admitted decode before it is released,
  * SLO autoscaler: ramping arrival grows the replica set, subsiding load
    drains it back down -- no dropped in-flight requests, invariants
    checked at every virtual tick.

Plus the property that routed execution over K replicas is
completion-equivalent to one local engine, and the satellite regressions:
actor hosts are excluded from idle-exit / idle scale-down, and preemption
notices drain with zero hot-producer re-execution.
"""
import threading
import time
from functools import partial

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover -- bare container
    from _hypothesis_fallback import given, settings, strategies as st

from _invariants import check_invariants, check_metrics_conformance
from repro.core import SimCluster, SimCostModel, SyndeoCluster
from repro.core.autoscaler import (AutoscalerConfig, ReplicaAutoscaler,
                                   ReplicaScalingConfig)
from repro.core.rendezvous import FileRendezvous
from repro.core.worker import HeadServer, _request, run_worker
from repro.serve.engine import Request, StubEngine
from repro.serve.fleet import actor_caller
from repro.serve.router import ActorReplicaHandle, ReplicaActor, Router


def _sim(n_workers=4, **cost_kw):
    cost = SimCostModel(task_time_s=lambda s: 0.05,
                        result_bytes=lambda s: 1024.0, jitter=0.0,
                        data_plane="p2p", result_location="worker",
                        **cost_kw)
    sim = SimCluster(cost)
    sim.add_workers(n_workers)
    return sim


def _reqs(n, tokens=6, offset=0):
    return [Request(id=offset + i, prompt=[offset + i, 17],
                    max_new_tokens=tokens) for i in range(n)]


def _expect(req):
    return StubEngine.stub_output(req.prompt, req.max_new_tokens)


# ------------------------------------------------ router admission basics


def test_router_fills_free_slots_before_queueing():
    r = Router(max_queue_per_replica=4)
    r.add_replica("r0", StubEngine(2))
    r.add_replica("r1", StubEngine(2))
    for q in _reqs(4):
        assert r.submit(q)
    # token-level admission: 4 requests over 2x2 slots -- both replicas
    # full, neither queueing while the other has a free slot
    assert all(h.free_slots == 0 for h in r.replicas.values())
    assert all(h.queue_len == 2 for h in r.replicas.values())


def test_router_sheds_to_retry_then_drops():
    r = Router(max_queue_per_replica=1, max_retry_backlog=2)
    r.add_replica("r0", StubEngine(1))
    accepted = [r.submit(q) for q in _reqs(8, tokens=4)]
    # 1 queue place (slot-bound request included), 2 park in retry, rest shed
    assert accepted.count(True) == 3
    assert r.stats["shed"] == 5
    done = r.flush()
    assert len(done) == 3            # retry buffer drained back in
    assert r.stats["retried"] >= 2


def test_routed_outputs_match_local_engine():
    reqs = _reqs(12, tokens=5)
    r = Router()
    for i in range(3):
        r.add_replica(f"r{i}", StubEngine(2))
    for q in reqs:
        assert r.submit(q)
    done = r.flush()
    assert sorted(q.id for q in done) == sorted(q.id for q in reqs)
    for q in reqs:
        assert q.done and q.output == _expect(q)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(1, 12), min_size=1, max_size=40),
       st.integers(1, 4), st.integers(1, 4))
def test_routed_execution_completion_equivalent(budgets, n_replicas, slots):
    """Property: routing a random request stream over K replicas completes
    exactly the same set of requests with exactly the same outputs as one
    local engine running the whole stream."""
    stream = [Request(id=i, prompt=[i % 7, len(budgets)], max_new_tokens=b)
              for i, b in enumerate(budgets)]
    local = StubEngine(slots)
    for q in stream:
        local.add_request(Request(id=q.id, prompt=list(q.prompt),
                                  max_new_tokens=q.max_new_tokens))
    reference = {q.id: q.output for q in local.run_until_drained(
        max_ticks=100000)}

    router = Router(max_queue_per_replica=3, max_retry_backlog=1000)
    for i in range(n_replicas):
        router.add_replica(f"r{i}", StubEngine(slots))
    for q in stream:
        assert router.submit(q)
    done = router.flush(max_ticks=100000)
    assert sorted(q.id for q in done) == sorted(reference)
    for q in done:
        assert q.output == reference[q.id]


# ------------------------------------------------------- chaos scenarios


def test_replica_death_mid_decode_rerouted_not_lost():
    sim = _sim(3)
    router = Router(clock=lambda: sim.now)
    for i in range(2):
        h = sim.add_replica(f"r{i}", batch_slots=2)
        router.add_replica(f"r{i}", h)
    reqs = _reqs(10, tokens=8)
    for q in reqs:
        assert router.submit(q)
    for _ in range(3):               # some decodes are genuinely mid-flight
        router.tick()
    victim = sim.replicas["r0"]
    assert any(len(router._inflight[rid]) for rid in router.replicas)
    sim.scheduler.on_worker_failed(victim.worker_id, reason="chaos")
    rerouted = router.fail_replica("r0")
    assert rerouted > 0
    done = router.flush()
    assert sorted(q.id for q in reqs) == sorted(q.id for q in done)
    for q in reqs:                   # re-decode reproduced identical tokens
        assert q.output == _expect(q)
    assert "r0" not in sim.scheduler.actors
    check_invariants(sim.store)
    check_metrics_conformance(sim.store, sim.scheduler, router=router)


def test_router_death_replicas_quiesce_and_reregister():
    sim = _sim(3)
    handles = {f"r{i}": sim.add_replica(f"r{i}", batch_slots=2)
               for i in range(2)}
    router = Router(clock=lambda: sim.now)
    for rid, h in handles.items():
        router.add_replica(rid, h)
    first = _reqs(8, tokens=6)
    for q in first:
        assert router.submit(q)
    for _ in range(2):
        router.tick()
    del router                        # the router process dies

    router2, recovered = Router.recover(dict(handles),
                                        clock=lambda: sim.now)
    # everything the dead router admitted into engines was finished by the
    # quiesce -- nothing is lost, outputs still correct
    for q in recovered:
        assert q.output == _expect(q)
    assert len(router2.replicas) == 2
    second = _reqs(6, tokens=4, offset=100)
    for q in second:
        assert router2.submit(q)
    done = router2.flush()
    assert {q.id for q in recovered} | {q.id for q in done} >= \
        {q.id for q in first}
    for q in second:
        assert q.output == _expect(q)
    check_invariants(sim.store)
    check_metrics_conformance(sim.store, sim.scheduler, router=router2)


def test_weight_broadcast_during_scale_up_zero_head_bytes():
    sim = _sim(5)
    weights = sim.store.put("w0", b"W" * 4096, ref_id="model-v1",
                            size_hint=64 << 20)
    joined = []

    def on_round(k):
        # scale-up lands MID-broadcast: the new replica pulls its weights
        # from the nearest fresh holder, not the producer or the head
        if k == 1 and not joined:
            h = sim.add_replica("r-late", batch_slots=2, weights=weights)
            joined.append(h)

    sim.store.broadcast(weights, ["w1", "w2", "w3"], on_round=on_round)
    assert joined and joined[0] is not None
    locs = sim.store.locations(weights)
    assert {"w0", "w1", "w2", "w3", joined[0].worker_id} <= locs
    assert sim.store.stats["head_relayed_bytes"] == 0
    assert joined[0].weights_version == weights.id
    # replica coherence across every landed copy + directory sanity
    check_invariants(sim.store, expect_fetchable=[weights.id])
    check_metrics_conformance(sim.store, sim.scheduler)


def test_drain_with_inflight_requests_completes_them():
    sim = _sim(3)
    router = Router(clock=lambda: sim.now)
    for i in range(2):
        router.add_replica(f"r{i}", sim.add_replica(f"r{i}", batch_slots=2))
    reqs = _reqs(9, tokens=7)
    for q in reqs:
        assert router.submit(q)
    for _ in range(2):
        router.tick()
    inflight_on_r0 = set(router._inflight["r0"])
    assert inflight_on_r0
    finished = router.retire_replica("r0")      # drain, not drop
    assert inflight_on_r0 <= {q.id for q in finished}
    sim.remove_replica("r0")
    assert "r0" not in sim.scheduler.actors
    done = router.flush()
    assert sorted(q.id for q in reqs) == sorted(
        q.id for q in finished + done)
    for q in reqs:
        assert q.output == _expect(q)
    check_invariants(sim.store)
    check_metrics_conformance(sim.store, sim.scheduler, router=router)


# ----------------------------------------------- SLO-driven autoscaling


def test_slo_autoscaler_grows_under_ramp_and_drains_when_quiet():
    sim = _sim(6)
    weights = sim.store.put("w5", b"W" * 2048, ref_id="model-v2",
                            size_hint=32 << 20)
    # a small p99 window: the quiet phase's fast completions must be able
    # to flush the burst-era samples out, or scale-down can never trigger
    router = Router(max_queue_per_replica=6, max_retry_backlog=4096,
                    p99_window=16, clock=lambda: sim.now)
    router.add_replica("r0", sim.add_replica("r0", batch_slots=4,
                                             weights=weights))
    next_id = [1]
    drained_out = []

    def grow(count):
        added = 0
        for _ in range(count):
            rid = f"r{next_id[0]}"
            h = sim.add_replica(rid, batch_slots=4, weights=weights)
            if h is None:
                break
            router.add_replica(rid, h)
            next_id[0] += 1
            added += 1
        return added

    def shrink(count):
        removed = 0
        # retire the most recently added first; never the last replica
        for rid in sorted(router.replicas, reverse=True)[:count]:
            if len(router.replicas) <= 1:
                break
            drained_out.extend(router.retire_replica(rid))
            sim.remove_replica(rid)
            removed += 1
        return removed

    ras = ReplicaAutoscaler(
        router, grow, shrink,
        ReplicaScalingConfig(min_replicas=1, max_replicas=4,
                             p99_target_ms=150.0, queue_depth_target=3.0,
                             low_water_fraction=0.5,
                             scale_up_cooldown_s=0.05,
                             scale_down_cooldown_s=0.4, max_step=2),
        clock=lambda: sim.now)

    # ramp: 140 requests at 200/s >> one replica's capacity, then quiet
    # trickle: 30 requests at 10/s << capacity
    arrivals = [(0.01 + 0.005 * i, q) for i, q in
                enumerate(_reqs(140, tokens=8))]
    arrivals += [(1.0 + 0.1 * i, q) for i, q in
                 enumerate(_reqs(30, tokens=4, offset=1000))]
    peak = [0]

    def on_tick(now):
        peak[0] = max(peak[0], len(router.replicas))
        check_invariants(sim.store)     # invariants hold THROUGHOUT

    completed = sim.run_serve(router, arrivals, tick_every=0.01,
                              drain_s=2.0, on_tick=on_tick,
                              replica_autoscaler=ras)
    all_done = completed + drained_out
    assert sorted(q.id for q in all_done) == sorted(
        q.id for _, q in arrivals)      # nothing dropped, ramp or drain
    for _, q in arrivals:
        assert q.output == _expect(q)
    assert peak[0] > 1, "ramp never grew the replica set"
    assert len(router.replicas) == 1, "quiet load did not drain replicas"
    assert any(e.action == "scale_up" for e in ras.events)
    assert any(e.action == "scale_down" for e in ras.events)
    assert sim.store.stats["head_relayed_bytes"] == 0   # weights were p2p
    check_invariants(sim.store, expect_fetchable=[weights.id])
    check_metrics_conformance(sim.store, sim.scheduler, router=router,
                              prom=sim.export_prometheus(router))


def test_replica_autoscaler_reacts_to_p99():
    r = Router(p99_window=16, clock=lambda: 100.0)
    r.add_replica("r0", StubEngine(2))
    r._latencies.extend([0.5] * 16)     # p99 = 500ms, target 150ms
    grown = []
    ras = ReplicaAutoscaler(r, lambda c: grown.append(c) or c,
                            lambda c: 0,
                            ReplicaScalingConfig(p99_target_ms=150.0,
                                                 queue_depth_target=100.0),
                            clock=lambda: 100.0)
    ev = ras.tick()
    assert ev is not None and ev.action == "scale_up" and grown
    assert "p99" in ev.reason


# --------------------------------- satellite: preemption-aware scale-down


def test_preempt_worker_drains_and_hands_off_before_deadline():
    sim = _sim(4)
    router = Router(clock=lambda: sim.now)
    h0 = sim.add_replica("r0", batch_slots=2)      # lands on w0 (least id)
    router.add_replica("r0", h0)
    victim_wid = h0.worker_id
    # hot objects solely held by the victim: the drain plane must migrate
    # them inside the notice window, never recompute them
    hot = [sim.store.put(victim_wid, {"shard": i}, ref_id=f"hot-{i}",
                         size_hint=1 << 20) for i in range(3)]
    reqs = _reqs(6, tokens=6)
    for q in reqs:
        assert router.submit(q)
    router.tick()                                   # decodes in flight

    sim.preempt_worker_at(victim_wid, t=0.5, notice_s=5.0, router=router)
    # run to well before the revocation deadline: the node must already
    # have drained gracefully (the deadline event then fires as a no-op)
    sim.run(until=2.0)
    assert victim_wid not in sim.scheduler.workers
    sim.run()
    assert sim.scheduler.stats["actors_lost"] == 0
    # the handoff's retire drained every in-flight decode on the way out
    # (no request dropped), and a successor serves on a survivor
    for q in reqs:
        assert q.done and q.output == _expect(q)
    assert list(router.replicas) == ["r0+"]
    assert router.replicas["r0+"].worker_id != victim_wid
    after = _reqs(3, tokens=4, offset=50)
    for q in after:
        assert router.submit(q)
    done = router.flush()
    assert sorted(q.id for q in done) == sorted(q.id for q in after)
    for q in after:
        assert q.output == _expect(q)
    # zero hot-producer re-execution: migration moved the bytes
    check_invariants(sim.store, expect_fetchable=[r.id for r in hot],
                     scheduler=sim.scheduler,
                     expect_zero_reconstructions=True)
    check_metrics_conformance(sim.store, sim.scheduler, router=router)


def test_preempt_past_deadline_falls_back_to_failure_path():
    sim = _sim(2)
    # a replica that is never handed off (no router) wedges the drain:
    # the revocation deadline must still reclaim the node
    h = sim.add_replica("r0", batch_slots=2)
    sim.preempt_worker_at(h.worker_id, t=0.1, notice_s=1.0)
    sim.run()
    assert h.worker_id not in sim.scheduler.workers
    assert sim.scheduler.stats["actors_lost"] == 1
    check_invariants(sim.store)
    check_metrics_conformance(sim.store, sim.scheduler)


# ------------------- satellite: actor hosts are excluded from idle paths


def test_idle_scale_down_skips_actor_hosts():
    sim = _sim(3)
    sim.attach_autoscaler(AutoscalerConfig(
        min_workers=0, max_workers=4, idle_timeout_s=0.5,
        scale_down_cooldown_s=0.1))
    sim.add_replica("r0", batch_slots=2)            # lands on w0
    host = sim.replicas["r0"].worker_id
    for t in (1.0, 2.0, 3.0, 4.0):
        sim._post(t - sim.now, lambda: None)
        sim.run()
        sim.autoscaler.tick(sim.now)
    # idle workers were drained away; the actor host NEVER became a victim
    assert host in sim.scheduler.workers
    others = [w for w in sim.scheduler.workers if w != host]
    assert not others, f"idle workers survived: {others}"
    check_invariants(sim.store)
    check_metrics_conformance(sim.store, sim.scheduler)


# ----------------------- real sockets: actor lifecycle + idle-exit guard


def test_socket_actor_keeps_worker_alive_past_idle_timeout(tmp_path):
    """Regression (satellite 1): a worker hosting a live replica actor
    must NOT start the idle-exit leave handshake, however long the gap
    between requests; after the actor exits, the idle clock resumes and
    the worker leaves normally. Also smoke-tests the full actor lifecycle
    over real sockets: create -> call -> result -> exit."""
    cluster = SyndeoCluster(rendezvous=FileRendezvous(str(tmp_path)))
    server = HeadServer(cluster)
    server.attach()
    t = threading.Thread(
        target=run_worker, args=(str(tmp_path), cluster.cluster_id, "sv-w0"),
        kwargs={"max_idle_s": 1.0,
                "actor_factories": {"replica": ReplicaActor}},
        daemon=True)
    t.start()
    try:
        deadline = time.time() + 20
        while time.time() < deadline and not any(
                w.alive for w in cluster.scheduler.workers.values()):
            time.sleep(0.05)
        rpc = partial(_request, "127.0.0.1", server.port, cluster.token)
        made = rpc({"op": "actor_create", "factory": "replica",
                    "actor": "rep0", "kwargs": {"batch_slots": 2}})
        assert made["ok"] and made["worker"] == "sv-w0"
        cap = made["cap"]
        handle = ActorReplicaHandle(actor_caller(rpc, "rep0", cap, 10.0))
        router = Router()
        router.add_replica("rep0", handle)
        reqs = _reqs(3, tokens=4)
        for q in reqs:
            assert router.submit(q)
        done = router.flush(max_ticks=200)
        assert sorted(q.id for q in done) == sorted(q.id for q in reqs)
        for q in reqs:
            assert q.output == _expect(q)

        # idle gap far past max_idle_s with the actor still hosted: the
        # worker must stay (no leave handshake, no scale-down candidacy)
        time.sleep(2.5)
        w = cluster.scheduler.workers.get("sv-w0")
        assert w is not None and w.alive and "rep0" in w.actors

        # graceful exit releases the hold; NOW the idle clock runs again
        bye = rpc({"op": "actor_exit", "actor": "rep0", "cap": cap})
        assert bye["ok"]
        deadline = time.time() + 20
        while time.time() < deadline and (
                "rep0" in cluster.scheduler.actors
                or "sv-w0" in cluster.scheduler.workers):
            time.sleep(0.1)
        assert "rep0" not in cluster.scheduler.actors
        assert "sv-w0" not in cluster.scheduler.workers
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        server.shutdown()
        cluster.shutdown()


def test_socket_actor_create_failure_reaches_the_caller(tmp_path):
    """A replica factory that raises (a model that does not fit, a compile
    error) must not look like a replica that quietly vanished: the
    exception text reaches the caller, and the head releases the hold."""
    def broken(**kwargs):
        raise MemoryError("RESOURCE_EXHAUSTED: replica does not fit")

    cluster = SyndeoCluster(rendezvous=FileRendezvous(str(tmp_path)))
    server = HeadServer(cluster)
    server.attach()
    t = threading.Thread(
        target=run_worker, args=(str(tmp_path), cluster.cluster_id, "sv-w1"),
        kwargs={"max_idle_s": 1.0, "actor_factories": {"replica": broken}},
        daemon=True)
    t.start()
    try:
        deadline = time.time() + 20
        while time.time() < deadline and not any(
                w.alive for w in cluster.scheduler.workers.values()):
            time.sleep(0.05)
        rpc = partial(_request, "127.0.0.1", server.port, cluster.token)
        made = rpc({"op": "actor_create", "factory": "replica",
                    "actor": "rep0"})
        assert made["ok"]
        call = actor_caller(rpc, "rep0", made["cap"], timeout_s=10.0)
        with pytest.raises(RuntimeError,
                           match="MemoryError: RESOURCE_EXHAUSTED"):
            call({"kind": "stats"})
        deadline = time.time() + 20
        while time.time() < deadline and "rep0" in cluster.scheduler.actors:
            time.sleep(0.05)
        assert "rep0" not in cluster.scheduler.actors   # hold released
        # after the exit ack the head itself still names the cause
        with pytest.raises(RuntimeError, match="failed to start: MemoryError"):
            call({"kind": "stats"})
        deadline = time.time() + 20
        while time.time() < deadline and "sv-w1" in cluster.scheduler.workers:
            time.sleep(0.1)
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        server.shutdown()
        cluster.shutdown()
