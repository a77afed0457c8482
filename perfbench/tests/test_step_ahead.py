"""The per-layer metrics `engine.step_ahead_share` and `engine.step_wait_ms`,
read from the engine's `engine.tick` and `engine.step_wait` spans: reported by
a traced run only, hand-computed on synthetic spans, matched to a served
engine's own ticks, and None where there is nothing sound to read."""
import time

import jax
import pytest

from perfbench import harness
from perfbench.tests import smoke

AHEAD, WAIT = "engine.step_ahead_share", "engine.step_wait_ms"
LO, HI = 100.0, 200.0


@pytest.mark.parametrize("metric", [AHEAD, WAIT])
def test_step_ahead_metric_is_in_both_cells(metric):
    for cell in ("granite-8b.chat", "deepseek-llm-7b.docqa"):
        names = {m["name"] for m in harness.load_cell(cell).per_layer}
        assert metric in names


def test_traced_run_reports_the_step_ahead_metrics():
    res, _, _ = smoke.run(smoke.cell(), traced=True, seed=2**31 + 23)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < m[AHEAD] <= 100
    assert m[WAIT] >= 0
    res, _, _ = smoke.run(smoke.cell(), traced=False, seed=2**31 + 24)
    assert not {AHEAD, WAIT} & set(res["metrics"])


def _readings(traced: bool = True, lo: float = LO, hi: float = HI):
    return harness.Readings(smoke.cell(), None, 1,
                            probes=object() if traced else None, lo=lo, hi=hi)


@pytest.fixture
def ring(monkeypatch):
    from repro.core import metrics
    r = metrics.SpanRing()
    monkeypatch.setattr(metrics, "SPANS", r)
    return r


def _tick(ring, start, end, active, ahead, wait=None):
    """One tick and, where it read a launched step, its wait of `wait` s."""
    ring.record("engine.tick", start, end, active=active, syncs=ahead,
                ahead=ahead)
    if wait is not None:
        ring.record("engine.step_wait", start, start + wait)


def test_ahead_share_counts_the_decoding_ticks_that_read_ahead(ring):
    _tick(ring, 110.0, 110.5, active=2, ahead=0)        # into an idle engine
    _tick(ring, 111.0, 111.2, active=2, ahead=1, wait=0.01)
    _tick(ring, 112.0, 112.2, active=3, ahead=1, wait=0.03)
    _tick(ring, 113.0, 113.2, active=1, ahead=1, wait=0.02)
    _tick(ring, 114.0, 114.1, active=0, ahead=0)        # idle: not counted
    assert harness.reader(AHEAD)(_readings()) == pytest.approx(75.0)
    assert harness.reader(WAIT)(_readings()) == pytest.approx(20.0)


def test_step_ahead_spans_before_the_window_are_ignored(ring):
    _tick(ring, 90.0, 91.0, active=2, ahead=0)
    _tick(ring, 91.0, 91.5, active=2, ahead=1, wait=0.4)
    _tick(ring, 110.0, 110.5, active=2, ahead=1, wait=0.002)
    assert harness.reader(AHEAD)(_readings()) == pytest.approx(100.0)
    assert harness.reader(WAIT)(_readings()) == pytest.approx(2.0)


def test_step_ahead_none_on_a_program_that_does_not_launch_ahead(ring):
    # an engine that does not launch ahead: its ticks count no `ahead`, and
    # no tick waits on a launched step
    ring.record("engine.tick", 110.0, 110.5, active=2, syncs=3)
    assert harness.reader(AHEAD)(_readings()) is None
    assert harness.reader(WAIT)(_readings()) is None


@pytest.mark.parametrize("metric", [AHEAD, WAIT])
def test_step_ahead_none_untraced_or_past_the_ring(ring, metric):
    _tick(ring, 110.0, 110.5, active=2, ahead=1, wait=0.01)
    assert harness.reader(metric)(_readings(traced=False)) is None
    ring.horizon = LO
    assert harness.reader(metric)(_readings()) is None


@pytest.fixture(scope="module")
def engine():
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    c = smoke.cell()
    model = build_model(c.family.model_config(c.cfg))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    return ServeEngine(model, params, batch_slots=3, max_len=64)


def test_step_ahead_readers_match_a_served_engines_ticks(engine):
    from repro.core.metrics import SPANS
    from repro.serve.engine import Request
    lo = time.perf_counter()
    for i in range(5):
        engine.add_request(Request(id=i, prompt=[5] * (8 + i),
                                   max_new_tokens=3 + 2 * i))
    engine.run_until_drained()
    hi = time.perf_counter()
    r = _readings(lo=lo, hi=hi)
    ticks = [s for s in SPANS.spans()
             if s.name == "engine.tick" and lo <= s.start < hi]
    waits = [s for s in SPANS.spans()
             if s.name == "engine.step_wait" and lo <= s.start < hi]
    # one idle engine, so one tick that read no launched step: the first
    assert [t.attrs["ahead"] for t in ticks] == [0] + [1] * (len(ticks) - 1)
    assert len(waits) == len(ticks) - 1
    assert harness.reader(AHEAD)(r) == pytest.approx(
        100.0 * (len(ticks) - 1) / len(ticks))
    assert harness.reader(WAIT)(r) == pytest.approx(
        1e3 * sum(w.end - w.start for w in waits) / len(waits))
