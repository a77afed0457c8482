"""The per-layer metrics read from the program's own spans
(`perfbench/spans.py`, `metrics/wire.*`, `metrics/engine.*`), on the CPU at
smoke size: reported by a traced run only, consistent with the round trip
timed from outside, and equal to the counts taken where the work happens."""
import tempfile
import time
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from perfbench import harness, programs
from perfbench.tests import smoke

SPAN_METRICS = ("wire.outbox_wait_ms", "wire.result_post_wait_ms",
                "wire.result_held_ms", "wire.result_polls_per_call",
                "engine.queue_wait_ms", "engine.admit_ms",
                "engine.host_syncs_per_step", "engine.compiles_in_window")


def test_span_metrics_are_in_the_cell():
    names = {m["name"] for m in harness.load_cell("granite-8b.chat").per_layer}
    assert set(SPAN_METRICS) <= names


def test_traced_run_reports_span_metrics_that_add_up():
    res, _, _ = smoke.run(smoke.cell(), traced=True, seed=2**31 + 21)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(SPAN_METRICS) <= set(m)
    assert m["engine.compiles_in_window"] == 0       # the warm-up held
    assert m["wire.result_polls_per_call"] >= 1
    assert m["engine.host_syncs_per_step"] >= 1
    for k in SPAN_METRICS:
        assert m[k] >= 0
    parts = (m["wire.outbox_wait_ms"] + m["wire.result_post_wait_ms"]
             + m["wire.result_held_ms"])
    assert parts <= m["wire.actor_rtt_ms"] + 1.0


def test_untraced_run_reports_no_span_metric():
    res, _, _ = smoke.run(smoke.cell(), traced=False, seed=2**31 + 22)
    assert not set(SPAN_METRICS) & set(res["metrics"])


@pytest.fixture(scope="module")
def engine():
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    c = smoke.cell()
    model = build_model(harness.model_config(c.cfg))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    return ServeEngine(model, params, batch_slots=3, max_len=64)


def test_host_syncs_per_step_is_the_reads_counted(engine):
    from repro.serve.engine import Request
    reads = []
    read = engine._read

    def counted(x):
        reads.append(1)
        return read(x)

    engine._read = counted
    ticks = engine.stats["ticks"]
    try:
        lo = time.perf_counter()
        for i in range(5):
            engine.add_request(Request(id=i, prompt=[5] * (8 + i),
                                       max_new_tokens=3 + 2 * i))
        engine.run_until_drained()
        hi = time.perf_counter()
    finally:
        del engine._read
    steps = engine.stats["ticks"] - ticks
    r = harness.Readings(smoke.cell(), None, 1, probes=object(), lo=lo, hi=hi)
    got = harness.reader("engine.host_syncs_per_step")(r)
    assert got == pytest.approx(len(reads) / steps)
    assert harness.reader("engine.compiles_in_window")(r) >= 0
    # an untraced run, or a window the ring no longer holds whole
    assert harness.reader("engine.host_syncs_per_step")(
        harness.Readings(smoke.cell(), None, 1, lo=lo, hi=hi)) is None
    from repro.core.metrics import SPANS
    r.lo = SPANS.horizon
    assert harness.reader("engine.admit_ms")(r) is None


def test_one_traced_program_matches_the_decode_step(engine):
    from repro.serve.engine import Request
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            engine.add_request(Request(id=9, prompt=[2] * 8, max_new_tokens=3))
            engine.run_until_drained()
        finally:
            jax.profiler.stop_trace()
        (path,) = Path(d).rglob("*.xplane.pb")
        fns = {ev.name for plane in ProfileData.from_file(str(path)).planes
               for line in plane.lines for ev in line.events
               if ev.name.startswith("PjitFunction(")}
    names = {"jit_" + f[len("PjitFunction("):-1] for f in fns}
    assert [n for n in names if programs._match(n, programs.DECODE)] \
        == ["jit__decode_step"]
    assert [n for n in names if programs._match(n, programs.PREFILL)] \
        == ["jit__prefill_impl"]
