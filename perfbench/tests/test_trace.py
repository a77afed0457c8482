"""The trace reduction: interval arithmetic, gap naming, and a small trace
recorded on a TPU v5e by `perfbench/record_trace.py` (committed under
`testdata/small_trace`)."""
import json

import pytest

from perfbench import programs, trace
from perfbench.harness import BENCH

SMALL = BENCH / "testdata" / "small_trace"


def test_merge_clip_gaps():
    ivs = [(5, 7), (0, 2), (1, 3), (6, 9), (12, 20)]
    busy = trace.merge(trace.clip(ivs, (1, 15)))
    assert busy == [(1, 3), (5, 9), (12, 15)]
    assert trace.gaps(busy, (1, 15)) == [(3, 5), (9, 12)]
    assert trace.gaps([], (0, 4)) == [(0, 4)]


def test_gap_named_by_most_specific_open_span():
    spans = {"router.tick": trace.merge([(0, 100)]),
             "wire.call": trace.merge([(10, 50)]),
             "engine.tick": trace.merge([(20, 30)])}
    assert trace.name_gap((20, 30), spans) == "engine.tick"
    assert trace.name_gap((40, 50), spans) == "wire.call"
    assert trace.name_gap((60, 80), spans) == "router.tick"
    assert trace.name_gap((200, 300), spans) == "host.other"


def test_program_names_drop_the_id():
    assert trace.program_name("jit__prefill_impl(1234)") == "jit__prefill_impl"
    assert trace.program_name("jit_add") == "jit_add"


@pytest.mark.parametrize("names, want", [
    (["jit__unknown", "jit__prefill_impl", "jit_scatter"], (0.003, 2)),
    (["jit_lm_decode_step", "jit__prefill_impl"], (0.003, 2)),
    (["jit__unknown_wrapper", "jit__prefill_impl"], (0.0, 0)),
    (["jit__unknown", "jit_lm_decode_step"], ValueError),
])
def test_decode_step_is_one_program(names, want):
    tr = trace.Trace(window=(0, 10**9), busy_ns={0: 1},
                     programs={n: [(0, 10**6), (5, 2 * 10**6)]
                               for n in names})
    if want is ValueError:
        with pytest.raises(ValueError):
            programs.one_program_s(tr, programs.DECODE)
    else:
        secs, n = programs.one_program_s(tr, programs.DECODE)
        assert (round(secs, 9), n) == want
    assert programs.one_program_s(tr, programs.PREFILL)[1] == \
        2 * ("jit__prefill_impl" in names)


def _recorded():
    found = sorted(SMALL.rglob("*.xplane.pb"))
    assert found, "the recorded trace is committed"
    return trace.read(found[-1]), json.loads((SMALL / "expect.json")
                                             .read_text())


def test_recorded_trace_programs_and_window():
    tr, want = _recorded()
    assert tr is not None and want["kind"] == "TPU v5 lite"
    assert list(tr.busy_ns) == [0]
    # the trace aligns the device's clock with the host's to about a
    # millisecond; in this recording the first matmul, dispatched as the
    # window opened, lies 1.0 ms before the window span, so 4 of its 5
    # executions fall inside
    found = {"bench_matmul": 4, "bench_add": 3}
    assert set(found) == set(want["programs"])
    for name, n in found.items():
        secs, k = programs.device_s(tr, (name,))
        assert k == n and secs > 0
    # the window span is what the host clock saw, to within a millisecond
    assert tr.window_s == pytest.approx(want["window_host_s"], abs=1e-3)


def test_recorded_trace_busy_and_idle():
    tr, want = _recorded()
    progs = programs.device_s(tr, tuple(want["programs"]))[0]
    # busy is the union of the ops inside the programs: never more than
    # the programs' own time, and the matmuls dominate it
    assert 0.5 * progs < tr.busy_s <= progs + 1e-6
    idle = sum(tr.idle_by_span.values()) / 1e9
    assert tr.busy_s + idle == pytest.approx(tr.window_s, rel=1e-6)
    # the sleep is device idle, named by the span the host was in
    assert tr.idle_by_span["client.idle"] / 1e9 >= want["sleep_s"] * 0.95
    # a 4096^3 bf16 matmul cannot beat the chip's peak
    mm_s, n = programs.device_s(tr, ("bench_matmul",))
    assert want["matmul_flops"] * n / mm_s < 197e12


def test_breakdown_lists_programs_and_idle():
    tr, _ = _recorded()
    b = trace.breakdown(tr)
    assert b["device_ops"][0][0].endswith("bench_matmul")
    assert b["idle_gaps"][0][0] == "client.idle"
    assert all(len(b[k]) <= 10 for k in b)
