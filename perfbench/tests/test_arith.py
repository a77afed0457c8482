"""Latency and throughput arithmetic of the end-to-end readers."""
import numpy as np
import pytest

from perfbench import harness
from perfbench.tests import smoke


def readings(win):
    return harness.Readings(smoke.cell(), win, 1)


def test_latency_throughput_and_failures_at_the_cap():
    # five requests due at 0..4 s in a 10 s window with a 5 s cap; #3
    # finishes after the window, #4 never
    win = harness.Window(seconds=10.0, cap_s=5.0,
                         sched=[0.0, 1.0, 2.0, 3.0, 4.0],
                         done=[2.0, 4.0, 9.0, 12.0, None],
                         n_out=[10, 20, 30, 40, 0], budget=[10, 20, 30, 40, 50])
    assert win.failed == 1
    assert win.latency_s() == [2.0, 3.0, 7.0, 9.0, 11.0]
    r = readings(win)
    # tokens served for the window's requests, over the time to the last
    # answer (#3 at 12 s)
    assert harness.reader("out_tok_per_s")(r) == (10 + 20 + 30 + 40) / 12.0
    assert harness.reader("req_p90_ms")(r) == pytest.approx(
        np.percentile([2, 3, 7, 9, 11], 90) * 1e3)
    per_tok = [2 / 10, 3 / 20, 7 / 30, 9 / 40, 11 / 50]
    assert harness.reader("norm_p90_ms_per_tok")(r) == pytest.approx(
        np.percentile(per_tok, 90) * 1e3)
    win.setup_s = 12.5
    assert harness.reader("setup_s")(r) == 12.5


def test_host_clock_layer_readers():
    from perfbench.probes import Probes
    p = Probes()
    p.spans["router.tick"] = [(0.5, 0.6), (1.0, 1.3), (20.0, 21.0)]
    p.spans["router.submit"] = [(0.1, 0.2)]
    p.spans["engine.tick"] = [(1.1, 1.15), (1.2, 1.25)]
    p.tick_calls = [(1.0, 1.2), (1.2, 1.4)]
    win = harness.Window(10.0, 1.0, [0.0], [1.0], [1], [1])
    r = harness.Readings(smoke.cell(), win, 1, probes=p, lo=0.0, hi=10.0)
    assert harness.reader("router.tick_ms")(r) == pytest.approx(200.0)
    assert harness.reader("router.submit_ms")(r) == pytest.approx(100.0)
    assert harness.reader("wire.actor_rtt_ms")(r) == pytest.approx(150.0)
    # no trace: the device readers find nothing and report nothing
    for name in ("engine.decode_step_ms", "engine.prefill_ms_per_ktok",
                 "model.decode_hbm_roofline", "model.serve_mfu",
                 "device.idle_share"):
        assert harness.reader(name)(r) is None
