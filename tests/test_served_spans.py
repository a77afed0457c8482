"""Spans of the served path, recorded where the work happens: the parts
of one actor round trip (client, head, worker), and the engine's queue,
admissions, host syncs and compiles."""
import threading
import time

import jax
import pytest

from repro.configs import get_config
from repro.core.metrics import SPANS
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine, StubEngine
from repro.serve.fleet import serve_fleet

CALL_PARTS = ("head.outbox", "actor.handle", "head.held")


def _since(t0):
    return [s for s in SPANS.spans() if s.start >= t0]


def test_each_tick_call_is_split_into_its_parts_in_time_order():
    t0 = time.perf_counter()
    reqs = [Request(id=i, prompt=[i + 1, 2], max_new_tokens=3)
            for i in range(5)]
    with serve_fleet([StubEngine(batch_slots=2)]) as fleet:
        for r in reqs:
            assert fleet.router.submit(r)
        fleet.router.flush()
    assert all(r.done for r in reqs)
    spans = _since(t0)
    assert SPANS.horizon < t0
    ticks = [s for s in spans
             if s.name == "wire.call" and s.attrs["kind"] == "tick"]
    assert ticks
    for call in ticks:
        cid = call.attrs["call"]
        parts = {}
        for name in CALL_PARTS:
            (parts[name],) = [s for s in spans if s.name == name
                              and s.attrs["call"] == cid]
        out, handle, held = (parts[n] for n in CALL_PARTS)
        assert handle.attrs["kind"] == "tick"
        assert handle.thread != call.thread
        assert (call.start <= out.start <= out.end <= handle.start
                <= handle.end <= held.start <= held.end <= call.end)
        waits = (out.end - out.start) + (held.start - handle.end) \
            + (held.end - held.start)
        assert waits <= call.end - call.start
        assert call.attrs["polls"] >= 1
    submits = [s for s in spans if s.name == "router.submit"]
    assert sorted(s.attrs["req"] for s in submits) == [r.id for r in reqs]
    assert len([s for s in spans if s.name == "router.tick"]) \
        == fleet.router.stats["ticks"]


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg)
    return model, jax.jit(model.init_params)(jax.random.PRNGKey(0))


def _engine(smoke_model, slots=2):
    model, params = smoke_model
    return ServeEngine(model, params, batch_slots=slots, max_len=32)


def test_engine_counts_every_host_read_in_its_tick(smoke_model):
    engine = _engine(smoke_model)
    reads = []
    read = engine._read

    def counted(x):
        reads.append(1)
        return read(x)

    engine._read = counted
    reqs = [Request(id=100 + i, prompt=list(range(1, 2 + i)),
                    max_new_tokens=2 + i) for i in range(4)]
    t0 = time.perf_counter()
    for r in reqs:
        engine.add_request(r)
    engine.run_until_drained()
    spans = _since(t0)
    ticks = [s for s in spans if s.name == "engine.tick"]
    assert len(ticks) == engine.stats["ticks"]
    assert sum(s.attrs["syncs"] for s in ticks) == len(reads)
    for tick in ticks:
        admits = [s for s in spans
                  if s.name == "engine.admit" and s.parent == tick.id]
        assert all(a.attrs["syncs"] == 1 for a in admits)
        # one argmax read, one per admission, at most one per slot
        lo = 1 + len(admits)
        assert lo <= tick.attrs["syncs"] <= lo + tick.attrs["active"]
    for name in ("engine.queue", "engine.admit"):
        ids = sorted(s.attrs["req"] for s in spans if s.name == name)
        assert ids == [r.id for r in reqs]
    admit = {s.attrs["req"]: s for s in spans if s.name == "engine.admit"}
    for s in spans:
        if s.name == "engine.queue":
            assert s.end <= admit[s.attrs["req"]].start
    assert {a.attrs["prompt"] for a in admit.values()} == {1, 2, 3, 4}


def _held(t0, ids):
    return [s.attrs["held"] for s in _since(t0)
            if s.name == "engine.admit" and s.attrs["req"] in ids]


def test_admission_counts_the_slots_it_holds(smoke_model):
    engine = _engine(smoke_model)
    t0 = time.perf_counter()
    engine.add_request(Request(id=201, prompt=[1, 2], max_new_tokens=6))
    engine.tick()                       # into an idle engine
    assert _held(t0, {201}) == [0]
    engine.add_request(Request(id=202, prompt=[3, 4], max_new_tokens=2))
    engine.tick()                       # 201 is mid-answer
    assert _held(t0, {202}) == [1]
    engine.run_until_drained()
    # two admitted in one tick: the first is answering when the second is
    engine = _engine(smoke_model)
    t0 = time.perf_counter()
    for i in (203, 204):
        engine.add_request(Request(id=i, prompt=[5, 6], max_new_tokens=2))
    engine.tick()
    assert _held(t0, {203, 204}) == [0, 1]
    engine.run_until_drained()


def test_compile_inside_a_tick_is_recorded_under_it(smoke_model):
    engine = _engine(smoke_model)
    t0 = time.perf_counter()
    # a prompt length no other test uses: its prefill has to compile
    engine.add_request(Request(id=1, prompt=[3] * 11, max_new_tokens=1))
    engine.tick()
    spans = _since(t0)
    by_id = {s.id: s for s in spans}
    (tick,) = [s for s in spans if s.name == "engine.tick"]
    compiles = [s for s in spans if s.name == "engine.compile"]
    assert compiles
    for c in compiles:
        chain, p = [], c.parent
        while p is not None:
            chain.append(by_id[p].name)
            p = by_id[p].parent
        assert "engine.tick" in chain
        assert tick.start <= c.start <= c.end <= tick.end


def test_decode_step_program_is_named_after_it(smoke_model):
    engine = _engine(smoke_model)
    batch = {"tokens": engine.tokens, "positions": engine.positions}
    text = engine._decode.lower(engine.params, engine.cache, batch).as_text()
    assert text.startswith("module @jit__decode_step ")


# (id, prompt length, budget, tick it arrives at), max_len 32: 401 and 405
# fill max_len (30 + 2, 20 + 12) before their budgets, the rest end on
# their budgets
TRACES = {
    "staggered": [(401, 30, 8, 0), (402, 4, 3, 0), (403, 6, 5, 1),
                  (404, 3, 2, 2), (405, 20, 30, 3), (406, 5, 4, 4)],
    "burst": [(401, 30, 8, 0), (402, 4, 3, 0), (403, 6, 5, 0),
              (404, 3, 2, 0), (405, 20, 30, 0), (406, 5, 4, 0)],
}
SCHEDULE_SPANS = ("engine.queue", "engine.admit", "engine.tick")


def _schedule(engine, trace):
    """Feed `trace` to `engine` tick by tick, until it drains. Returns,
    per tick, what the router sees of the engine and what it handed back,
    and the engine's queue, admission and tick spans (less `syncs`, the
    host reads, which only the model-backed engine makes)."""
    reqs = {i: Request(id=i, prompt=[1 + (i + j) % 7 for j in range(n)],
                       max_new_tokens=budget) for i, n, budget, _ in trace}
    me, t0 = threading.get_ident(), time.perf_counter()
    rows, tick = [], 0
    while tick <= max(at for *_, at in trace) or engine.queue_len \
            or any(r is not None for r in engine.slot_req):
        for i, _, _, at in trace:
            if at == tick:
                engine.add_request(reqs[i])
        active = engine.tick()
        rows.append((active, [r and r.id for r in engine.slot_req],
                     engine.free_slots, engine.queue_len,
                     engine.outstanding_tokens,
                     sorted((r.id, len(r.output))
                            for r in engine.pop_completed())))
        tick += 1
    assert SPANS.horizon < t0
    spans = [s for s in _since(t0)
             if s.thread == me and s.name in SCHEDULE_SPANS]
    ticks = [s.id for s in spans if s.name == "engine.tick"]
    return rows, [(s.name, ticks.index(s.parent) if s.parent in ticks
                   else None,
                   {k: v for k, v in s.attrs.items() if k != "syncs"})
                  for s in spans]


@pytest.mark.parametrize("slots", [1, 2, 3])
@pytest.mark.parametrize("trace", sorted(TRACES))
def test_stub_and_served_engines_schedule_alike(smoke_model, trace, slots):
    model, params = smoke_model
    served = _schedule(
        ServeEngine(model, params, batch_slots=slots, max_len=32),
        TRACES[trace])
    stub = _schedule(StubEngine(batch_slots=slots, max_len=32),
                     TRACES[trace])
    assert stub[0] == served[0]
    assert stub[1] == served[1]
    rows, spans = stub
    lengths = dict(done for row in rows for done in row[-1])
    budgets = {i: budget for i, _, budget, _ in TRACES[trace]}
    assert lengths == {**budgets, 401: 2, 405: 12}
    assert [a["req"] for name, _, a in spans if name == "engine.admit"] \
        == [a["req"] for name, _, a in spans if name == "engine.queue"]
    assert [a["active"] for name, _, a in spans if name == "engine.tick"] \
        == [row[0] for row in rows]
