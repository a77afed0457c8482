"""Spans and counters around the calls into each layer, installed by the
benchmark (never inside the program) for a traced run only.

Each wrapper opens a `jax.profiler.TraceAnnotation` of the layer's name,
so the trace can name what the host did while the device idled, and keeps
the host-clock interval, plus what the call carried, for the readers:

    router.tick / router.submit   the harness's own calls (driver thread)
    wire.call                     `ActorReplicaHandle._call`, one actor
                                  round trip (driver thread)
    engine.tick                   `ServeEngine.tick` (worker thread)
    engine.prefill                the engine's jitted prefill, dispatch only
    engine.decode                 the engine's jitted decode step, dispatch
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Tuple

import jax


class Probes:
    def __init__(self):
        # name -> [(t0, t1)] on time.perf_counter
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.tick_calls: List[Tuple[float, float]] = []     # wire, kind tick
        self.prefills: List[Tuple[float, int]] = []         # (t, tokens)
        self.decodes: List[Tuple[float, List[int]]] = []    # (t, contexts)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def wrap_engine(self, engine: Any) -> None:
        tick, prefill, decode = engine.tick, engine._prefill_one, engine._decode

        def tick_probe():
            with self.span("engine.tick"):
                return tick()

        def prefill_probe(params, tokens):
            self.prefills.append((time.perf_counter(), int(tokens.shape[1])))
            with self.span("engine.prefill"):
                return prefill(params, tokens)

        def decode_probe(params, cache, batch):
            self.decodes.append((time.perf_counter(), [
                len(r.prompt) + len(r.output)
                for r in engine.slot_req if r is not None]))
            with self.span("engine.decode"):
                return decode(params, cache, batch)

        engine.tick = tick_probe
        engine._prefill_one, engine._decode = prefill_probe, decode_probe

    def wrap_handle(self, handle: Any) -> None:
        call = handle._call

        def call_probe(payload):
            t0 = time.perf_counter()
            with self.span("wire.call"):
                out = call(payload)
            if payload.get("kind") == "tick":
                self.tick_calls.append((t0, time.perf_counter()))
            return out

        handle._call = call_probe

    def within(self, items, lo: float, hi: float):
        """Entries of a list of (t, ...) or (t0, t1) that start in [lo, hi)."""
        return [x for x in items if lo <= x[0] < hi]
