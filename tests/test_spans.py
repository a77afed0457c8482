"""The span instrument of the observability plane (`core/metrics.py`):
nesting, parents and threads, counts taken in open spans, the ring's
bound and its wrap marker, recording turned off, and its two clocks --
memory always, the profiler's host plane once JAX is loaded."""
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from repro.core.metrics import SPANS, SpanRing

REPO = Path(__file__).resolve().parents[1]


def _named(ring):
    return {s.name: s for s in ring.spans()}


def test_nested_spans_carry_parent_thread_and_attrs():
    ring = SpanRing()
    with ring.span("outer", req=7) as outer:
        with ring.span("inner"):
            ring.record("measured", 1.0, 2.0, call="c-1")
        other = threading.Thread(target=lambda: ring.span("other")
                                 .__enter__().__exit__(None, None, None))
        other.start()
        other.join(timeout=10)
        assert not other.is_alive()
        outer.attrs["late"] = 3
    got = _named(ring)
    assert got["outer"].parent is None
    assert got["inner"].parent == got["outer"].id
    assert got["measured"].parent == got["inner"].id
    assert (got["measured"].start, got["measured"].end) == (1.0, 2.0)
    assert got["measured"].attrs == {"call": "c-1"}
    # a span opened on another thread has its own stack
    assert got["other"].parent is None
    assert got["other"].thread != got["outer"].thread
    assert got["outer"].attrs == {"req": 7, "late": 3}
    assert got["outer"].start <= got["inner"].start <= got["inner"].end \
        <= got["outer"].end
    assert len({s.id for s in ring.spans()}) == 4


def test_add_counts_in_every_open_span_that_declares_the_key():
    ring = SpanRing()
    ring.add("syncs")                       # nothing open: no effect
    with ring.span("tick", syncs=0):
        with ring.span("admit", syncs=0):
            ring.add("syncs")
        with ring.span("prefill"):
            ring.add("syncs", 2)
    got = _named(ring)
    assert got["tick"].attrs == {"syncs": 3}
    assert got["admit"].attrs == {"syncs": 1}
    assert got["prefill"].attrs == {}


def test_span_is_recorded_when_its_block_raises():
    ring = SpanRing()
    with pytest.raises(ValueError):
        with ring.span("failing"):
            raise ValueError("boom")
    with ring.span("after"):
        pass
    got = _named(ring)
    assert set(got) == {"failing", "after"}
    assert got["after"].parent is None      # the stack was unwound


def test_ring_keeps_the_newest_and_marks_what_it_dropped():
    ring = SpanRing()
    n = ring.capacity
    for t in range(n):
        ring.record("s", float(t), t + 0.5)
    assert ring.horizon == float("-inf")
    for t in range(n, n + 3):
        ring.record("s", float(t), t + 0.5)
    held = ring.spans()
    assert len(held) == n
    assert (held[0].start, held[-1].start) == (3.0, float(n + 2))
    # the first three were overwritten: every span ending after the
    # third one's end is held
    assert ring.horizon == 2.5
    assert all(s.end > ring.horizon for s in held)


def test_disabled_ring_records_nothing():
    ring = SpanRing()
    ring.enabled = False
    with ring.span("off", polls=0) as sp:
        sp.attrs["polls"] += 1              # callers may still write
        ring.add("polls")
        ring.record("off.record", 0.0, 1.0)
    assert ring.spans() == []
    assert sp.attrs == {"polls": 1}
    ring.enabled = True
    with ring.span("on"):
        pass
    assert [s.name for s in ring.spans()] == ["on"]


def test_recording_spans_does_not_import_jax():
    code = ("import sys\n"
            "from repro.core.metrics import SPANS\n"
            "with SPANS.span('a', syncs=0):\n"
            "    SPANS.add('syncs')\n"
            "    SPANS.record('b', 0.0, 1.0, call='c')\n"
            "assert len(SPANS.spans()) == 2\n"
            "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env={"PYTHONPATH": str(REPO / "src"),
                              "PATH": "/usr/bin:/bin"},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    """Once JAX is loaded a `span()` is also a TraceAnnotation, so a
    profiler trace holds it beside the device's events; `record()`
    intervals stay in memory."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with SPANS.span("test.spans.outer"):
            with SPANS.span("test.spans.inner"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
            now = time.perf_counter()
            SPANS.record("test.spans.recorded", now, now)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert {"test.spans.outer", "test.spans.inner"} <= names
    assert "test.spans.recorded" not in names
