"""A replica fleet on a live cluster: the served path, wired end to end.

    client -> Router -> ActorReplicaHandle -> head (actor_call) -> worker
           -> ReplicaActor -> engine

`serve_fleet(engines)` brings up a head (`SyndeoCluster` + `HeadServer`)
on localhost, one `run_worker` thread per engine whose `ReplicaActor`
wraps that engine, and one replica actor per worker; it yields a `Fleet`
whose router admits requests. On exit every replica drains and exits,
the workers leave and the head shuts down.

Everything runs in the calling process, so a replica whose engine holds
an accelerator keeps it in the one process that touched JAX: the head
side (`repro.core`) never builds an array.
"""
from __future__ import annotations

import contextlib
import functools
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Sequence

from repro.core.cluster import SyndeoCluster
from repro.core.metrics import SPANS
from repro.core.rendezvous import FileRendezvous
from repro.core.worker import HeadServer, _dec, _enc, _request, run_worker
from repro.serve.router import ActorReplicaHandle, ReplicaActor, Router


@dataclass
class Fleet:
    router: Router
    server: HeadServer             # the head: its `metrics` op, gauges


def actor_caller(rpc: Callable[[Dict[str, Any]], Dict[str, Any]],
                 actor: str, cap: Dict[str, Any],
                 timeout_s: float) -> Callable[[Dict[str, Any]], Any]:
    """Synchronous payload -> value transport to one replica actor over
    the head's actor_call / actor_result ops (`rpc` sends one message
    to the head). A refused call or a failed one (including a replica
    whose create failed) raises with the head's or the worker's error
    text."""
    def call(payload: Dict[str, Any]) -> Any:
        with SPANS.span("wire.call", kind=payload.get("kind"),
                        polls=0) as span:
            return round_trip(payload, span.attrs)

    def round_trip(payload: Dict[str, Any], attrs: Dict[str, Any]) -> Any:
        sent = rpc({"op": "actor_call", "actor": actor, "cap": cap,
                    "payload": _enc(payload)})
        if not sent.get("ok"):
            raise RuntimeError(f"replica {actor!r}: {sent.get('error')}")
        attrs["call"] = sent["call"]
        limit = time.monotonic() + timeout_s
        while time.monotonic() < limit:
            # the head holds the request until the result is stored (for
            # at most its cap), so one request normally brings it back
            got = rpc({"op": "actor_result", "call": sent["call"]})
            attrs["polls"] += 1
            if got.get("done"):
                if got.get("error"):
                    raise RuntimeError(f"replica {actor!r}: {got['error']}")
                return _dec(got["value"])
        raise TimeoutError(f"replica {actor!r}: no result for "
                           f"{payload.get('kind')!r} in {timeout_s} s")
    return call


def _wait(cond: Callable[[], bool], timeout_s: float, what: str):
    limit = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() > limit:
            raise TimeoutError(what)
        time.sleep(0.05)


@contextlib.contextmanager
def serve_fleet(engines: Sequence[Any], *, call_timeout_s: float = 60.0
                ) -> Iterator[Fleet]:
    """One replica actor per engine behind a `Router`. Engines should be
    built (and compiled) before this is entered: a replica call that
    compiles can outlast `call_timeout_s`."""
    with tempfile.TemporaryDirectory() as rdv_dir:
        cluster = SyndeoCluster(rendezvous=FileRendezvous(rdv_dir))
        server = HeadServer(cluster)
        server.attach()
        rpc = functools.partial(_request, "127.0.0.1", server.port,
                                cluster.token)
        workers = [f"replica-w{i}" for i in range(len(engines))]
        threads: List[threading.Thread] = []
        caps: Dict[str, Dict[str, Any]] = {}
        sched = cluster.scheduler
        try:
            for wid, engine in zip(workers, engines):
                factory = functools.partial(ReplicaActor, engine=engine)
                t = threading.Thread(
                    target=run_worker, args=(rdv_dir, cluster.cluster_id, wid),
                    kwargs={"max_idle_s": 1.0,
                            "actor_factories": {"replica": factory}},
                    name=f"fleet-{wid}", daemon=True)
                t.start()
                threads.append(t)
            _wait(lambda: all(w in sched.workers and sched.workers[w].alive
                              for w in workers), 60.0,
                  "replica workers did not join")
            router = Router(stats_sink=server.serve_stats.update)
            for i in range(len(engines)):
                made = rpc({"op": "actor_create", "factory": "replica",
                            "actor": f"rep{i}"})
                if not made.get("ok"):
                    raise RuntimeError(f"replica rep{i}: {made.get('error')}")
                rid = made["actor"]
                caps[rid] = made["cap"]
                # the handle's first stats call waits for the worker to
                # host the actor, and raises if its create failed
                router.add_replica(rid, ActorReplicaHandle(actor_caller(
                    rpc, rid, caps[rid], call_timeout_s)))
            yield Fleet(router, server)
        finally:
            for rid, cap in caps.items():
                rpc({"op": "actor_exit", "actor": rid, "cap": cap})
            with contextlib.suppress(TimeoutError):
                _wait(lambda: not any(w in sched.workers for w in workers),
                      60.0, "replica workers did not leave")
            server.shutdown()
            cluster.shutdown()
            for t in threads:
                t.join(timeout=10)
