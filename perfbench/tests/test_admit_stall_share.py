"""The per-layer metric `engine.admit_stall_share`, read from the engine's
`engine.admit` and `engine.tick` spans: hand-computed on synthetic spans,
and None where there is nothing sound to read."""
import pytest

from perfbench import harness
from perfbench.tests import smoke

METRIC = "engine.admit_stall_share"
LO, HI = 100.0, 200.0


def test_admit_stall_share_is_in_both_cells():
    for cell in ("granite-8b.chat", "deepseek-llm-7b.docqa"):
        names = {m["name"] for m in harness.load_cell(cell).per_layer}
        assert METRIC in names


def _readings(traced: bool = True):
    return harness.Readings(smoke.cell(), None, 1,
                            probes=object() if traced else None, lo=LO, hi=HI)


@pytest.fixture
def ring(monkeypatch):
    from repro.core import metrics
    r = metrics.SpanRing()
    monkeypatch.setattr(metrics, "SPANS", r)
    return r


def _tick(ring, start, end, active, admits=()):
    """One tick and the admissions inside it, each (wall, held)."""
    ring.record("engine.tick", start, end, active=active, syncs=0)
    t = start
    for wall, held in admits:
        ring.record("engine.admit", t, t + wall, req=0, prompt=8, syncs=1,
                    held=held)
        t += wall


def test_share_is_stalled_slot_time_over_answering_slot_time(ring):
    # two admissions in one tick: the first holds 2 slots for 0.1 s, the
    # second (the first now answering too) holds 3 for 0.2 s
    _tick(ring, 110.0, 110.5, active=4, admits=[(0.1, 2), (0.2, 3)])
    _tick(ring, 111.0, 111.25, active=4)
    _tick(ring, 112.0, 112.2, active=0)           # idle: not counted
    # an admission into an idle engine holds no slot
    _tick(ring, 113.0, 113.3, active=1, admits=[(0.2, 0)])
    stalled = 0.1 * 2 + 0.2 * 3
    answering = 0.5 * 4 + 0.25 * 4 + 0.3 * 1
    got = harness.reader(METRIC)(_readings())
    assert got == pytest.approx(100 * stalled / answering)
    assert 0 <= got <= 100


def test_spans_before_the_window_are_ignored(ring):
    _tick(ring, 90.0, 91.0, active=6, admits=[(0.9, 5)])
    _tick(ring, 110.0, 110.5, active=2, admits=[(0.1, 1)])
    _tick(ring, 111.0, 111.5, active=2)
    got = harness.reader(METRIC)(_readings())
    assert got == pytest.approx(100 * 0.1 / (0.5 * 2 + 0.5 * 2))


def test_none_untraced(ring):
    _tick(ring, 110.0, 110.5, active=2, admits=[(0.1, 1)])
    assert harness.reader(METRIC)(_readings(traced=False)) is None


def test_none_on_a_program_without_held(ring):
    ring.record("engine.tick", 110.0, 110.5, active=2, syncs=0)
    ring.record("engine.admit", 110.0, 110.1, req=0, prompt=8, syncs=1)
    assert harness.reader(METRIC)(_readings()) is None


def test_none_when_the_ring_has_passed_the_window(ring):
    _tick(ring, 110.0, 110.5, active=2, admits=[(0.1, 1)])
    ring.horizon = LO
    assert harness.reader(METRIC)(_readings()) is None


def test_none_without_the_recorder(ring, monkeypatch):
    from repro.core import metrics
    monkeypatch.delattr(metrics, "SPANS")
    assert harness.reader(METRIC)(_readings()) is None
