"""Spans of the served path, recorded where the work happens: the parts
of one actor round trip (client, head, worker), and the engine's queue,
admissions, host syncs and compiles."""
import threading
import time

import jax
import jax.numpy as jnp
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
    reads = []                  # per read: was it of the step's tokens
    read = engine._read

    def counted(x):
        reads.append(x is engine.tokens)
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
        # the in-flight step's tokens, and one per admission: no other
        # read, of a slot's position or anything else
        assert tick.attrs["syncs"] == tick.attrs["ahead"] + len(admits)
    # every read outside an admission is of the launched step's tokens
    assert reads.count(True) == sum(t.attrs["ahead"] for t in ticks)
    for name in ("engine.queue", "engine.admit"):
        ids = sorted(s.attrs["req"] for s in spans if s.name == name)
        assert ids == [r.id for r in reqs]
    admit = {s.attrs["req"]: s for s in spans if s.name == "engine.admit"}
    for s in spans:
        if s.name == "engine.queue":
            assert s.end <= admit[s.attrs["req"]].start
    assert {a.attrs["prompt"] for a in admit.values()} == {1, 2, 3, 4}


def test_tick_returns_with_the_next_step_in_flight(smoke_model):
    engine = _engine(smoke_model)
    events = []                 # ("read" | "launch", the array it touched)
    read, decode = engine._read, engine._decode

    def read_tap(x):
        events.append(("read", x))
        return read(x)

    def decode_tap(params, cache, batch):
        out = decode(params, cache, batch)
        events.append(("launch", out[0][0]))
        return out

    engine._read, engine._decode = read_tap, decode_tap
    engine.add_request(Request(id=301, prompt=[1, 2, 3], max_new_tokens=4))
    t0 = time.perf_counter()
    assert engine.tick() == 1           # admits 301, launches its step
    assert [e for e, _ in events] == ["read", "launch"]
    assert engine._stepping == [0]
    assert engine.slot_req[0].output and len(engine.slot_req[0].output) == 1
    launched = events[-1][1]
    events.clear()
    assert engine.tick() == 1           # reads that step, launches the next
    assert [e for e, _ in events] == ["read", "launch"]
    assert events[0][1] is launched
    assert len(engine.slot_req[0].output) == 2
    engine.run_until_drained()
    spans = _since(t0)
    ticks = [s for s in spans if s.name == "engine.tick"]
    # the admitting tick read no step; every later one read its
    # predecessor's, the last launching none
    assert [t.attrs["ahead"] for t in ticks] == [0, 1, 1, 1]
    assert [t.attrs["active"] for t in ticks] == [1, 1, 1, 1]
    assert engine._stepping == []
    waits = [s for s in spans if s.name == "engine.step_wait"]
    assert [w.parent for w in waits] == [t.id for t in ticks[1:]]


def test_a_slot_freed_in_a_tick_is_refilled_in_it(smoke_model):
    engine = _engine(smoke_model, slots=1)
    first = Request(id=311, prompt=[1, 2], max_new_tokens=2)
    second = Request(id=312, prompt=[3, 4], max_new_tokens=2)
    engine.add_request(first)
    engine.add_request(second)
    engine.tick()                       # admits 311, 312 waits
    assert engine.slot_req == [first] and engine.queue_len == 1
    engine.tick()                       # 311 ends; 312 takes its slot
    assert engine.pop_completed() == [first] and len(first.output) == 2
    assert engine.slot_req == [second] and len(second.output) == 1
    assert engine._stepping == [0]
    engine.run_until_drained()
    assert len(second.output) == 2


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
    # a slot freed in a tick is refilled in that tick; the request that
    # freed it is no longer held
    engine = _engine(smoke_model)
    t0 = time.perf_counter()
    for i, budget in ((205, 6), (206, 2), (207, 2)):
        engine.add_request(Request(id=i, prompt=[1, 2], max_new_tokens=budget))
    engine.tick()                       # admits 205 and 206; 207 waits
    assert _held(t0, {205, 206, 207}) == [0, 1]
    assert engine.pop_completed() == [] and engine.queue_len == 1
    engine.tick()                       # 206 ends, 207 takes its slot
    assert [r.id for r in engine.pop_completed()] == [206]
    assert [r.id for r in engine.slot_req] == [205, 207]
    assert _held(t0, {207}) == [1]
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
    batch = {"tokens": engine.tokens, "positions": engine.positions,
             "live": engine.live}
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
    # a slot freed in a tick is refilled in it: no slot is left empty
    # while a request waits
    assert all(queued == 0 or None not in ids
               for _, ids, _, queued, _, _ in rows)
    if slots == 1:
        # the requests answer one after another, each holding the slot for
        # one tick per token after its first: one tick more than those
        assert len(rows) == 1 + sum(n - 1 for n in lengths.values())


def _straight(model, params, prompt, n, max_len):
    """`n` greedy tokens of `prompt` alone: prefill, then one decode step
    at a time on a one-row cache of `max_len`."""
    logits, small = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    cache = jax.tree.map(
        lambda big, s: jnp.pad(s, [(0, b - a) for b, a in
                                   zip(big.shape, s.shape)]).astype(big.dtype),
        model.init_cache(1, max_len), small)
    step = jax.jit(model.decode_step)
    out = [int(jnp.argmax(logits[0, -1]))]
    while len(out) < n:
        batch = {"tokens": jnp.asarray([[out[-1]]], jnp.int32),
                 "positions": jnp.asarray([len(prompt) + len(out) - 1],
                                          jnp.int32)}
        logits, cache = step(params, cache, batch)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


@pytest.mark.parametrize("slots", [2, 3])
@pytest.mark.parametrize("trace", sorted(TRACES))
def test_served_outputs_are_straight_decodes(smoke_model, trace, slots):
    model, params = smoke_model
    engine = ServeEngine(model, params, batch_slots=slots, max_len=32)
    reqs = {i: Request(id=i, prompt=[1 + (i + j) % 7 for j in range(n)],
                       max_new_tokens=budget)
            for i, n, budget, _ in TRACES[trace]}
    tick = 0
    while tick <= max(at for *_, at in TRACES[trace]) or engine.queue_len \
            or any(r is not None for r in engine.slot_req):
        for i, _, _, at in TRACES[trace]:
            if at == tick:
                engine.add_request(reqs[i])
        engine.tick()
        tick += 1
    assert all(r.done for r in reqs.values())
    for r in reqs.values():
        assert r.output == _straight(model, params, r.prompt, len(r.output),
                                     32), r.id
