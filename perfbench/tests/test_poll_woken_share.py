"""The per-layer metric `wire.poll_woken_share`, read from the head's
`head.poll_wait` spans, on the CPU at smoke size: in the cell, reported by a
traced run only, and equal to the share counted from the spans where the
polls happen. None on a program that holds no poll or has no recorder."""
import time

from perfbench import harness
from perfbench.tests import smoke

METRIC = "wire.poll_woken_share"


def test_poll_woken_share_is_in_the_cell():
    names = {m["name"] for m in harness.load_cell("granite-8b.chat").per_layer}
    assert METRIC in names


def test_traced_run_reports_poll_woken_share_as_a_share():
    res, _, _ = smoke.run(smoke.cell(), traced=True, seed=2**31 + 23)
    assert 0 <= res["metrics"][METRIC]["value"] <= 1


def test_untraced_run_reports_no_poll_woken_share():
    res, _, _ = smoke.run(smoke.cell(), traced=False, seed=2**31 + 24)
    assert METRIC not in res["metrics"]


def test_poll_woken_share_reads_the_polls_that_handed_over_calls(
        monkeypatch):
    from repro.core import metrics
    from repro.serve.engine import Request, StubEngine
    from repro.serve.fleet import serve_fleet
    read = harness.reader(METRIC)
    lo = time.perf_counter()
    with serve_fleet([StubEngine(batch_slots=2)]) as fleet:
        for i in range(6):
            assert fleet.router.submit(Request(id=i, prompt=[1, 2],
                                               max_new_tokens=3))
        fleet.router.flush()
    hi = time.perf_counter()
    r = harness.Readings(smoke.cell(), None, 1, probes=object(), lo=lo, hi=hi)
    held = [s for s in metrics.SPANS.spans() if lo <= s.start < hi]
    calls = {s.attrs["call"] for s in held if s.name == "wire.call"}
    polls = {s.end for s in held if s.name == "head.outbox"}
    woken = [s for s in held if s.name == "head.poll_wait"
             and s.attrs["woke"] and s.attrs["calls"]]
    assert woken and {c for s in woken for c in s.attrs["calls"]} <= calls
    assert len(polls) <= len(calls)
    assert 0 < read(r) <= 1
    # an untraced run
    assert read(harness.Readings(smoke.cell(), None, 1, lo=lo, hi=hi)) is None
    # a program that records its outbox waits but holds no poll
    ring = metrics.SpanRing()
    for s in held:
        if s.name == "head.outbox":
            ring.record(s.name, s.start, s.end, **s.attrs)
    monkeypatch.setattr(metrics, "SPANS", ring)
    assert read(r) is None
    # a program without the recorder
    monkeypatch.delattr(metrics, "SPANS")
    assert read(r) is None
