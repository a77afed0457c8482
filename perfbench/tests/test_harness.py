"""A smoke-size cell driven through serve_fleet on the CPU, and the faults
that the comparison has to catch."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from perfbench import harness
from perfbench.harness import ROOT
from perfbench.tests import smoke


def test_result_line_has_the_contract_shape():
    c = smoke.cell()
    res, checks, win = smoke.run(c)
    line = smoke.last_line(res)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] == len(win.done) == round(4.0 * 3.0)
    assert line["failed"] == 0
    want = {m["name"] for m in c.end_to_end}
    assert set(line["metrics"]) == want
    for m in c.end_to_end:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["checks"]) == set(c.limits)
    for v in line["checks"].values():
        assert set(v) == {"value", "limit"} and v["value"] <= v["limit"]


def test_traced_run_reports_per_layer_metrics_and_device_window():
    c = smoke.cell()
    res, _, _ = smoke.run(c, traced=True, seed=11)
    assert res["correct"] is True
    assert set(res["device"]) >= {"busy_s", "window_s"}
    assert res["device"]["window_s"] >= 3.0
    # the host-clock readers read on any backend; the device readers find
    # no TPU plane on the CPU and leave their metrics out
    assert {"router.tick_ms", "router.submit_ms",
            "wire.actor_rtt_ms"} <= set(res["metrics"])
    assert set(res["metrics"]) <= {m["name"] for m in c.per_layer}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _broken(monkeypatch, fault):
    from repro.models import dense
    step = dense.lm_decode_step

    def unchanged_state(params, cache, batch, **kw):
        logits, _ = step(params, cache, batch, **kw)
        return logits, cache

    def altered_token(params, cache, batch, **kw):
        logits, cache = step(params, cache, batch, **kw)
        return jnp.roll(logits, 1, axis=-1), cache

    monkeypatch.setattr(dense, "lm_decode_step",
                        {"unchanged_state": unchanged_state,
                         "altered_token": altered_token}[fault])


@pytest.mark.parametrize("fault", ["unchanged_state", "altered_token"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    _broken(monkeypatch, fault)
    res, checks, _ = smoke.run(smoke.cell(), seed=5)
    assert res["correct"] is False
    assert any(v > lim for v, lim in checks.values())


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         "granite-8b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("granite-8b.nothing")


def test_drive_counts_failures_at_the_cap():
    """A router that never finishes anything: every request due in the
    window fails and counts at the cap."""
    from perfbench import traffic

    class Stuck:
        def __init__(self):
            self.n = 0

        def submit(self, r):
            self.n += 1
            return self.n % 2 == 0          # every other one is shed

        def idle(self):
            return self.n == 0

        def tick(self):
            return []

    c = smoke.cell()
    reqs = traffic.generate(c.mix, 1.0, 3, 100)
    win, _ = harness.drive(Stuck(), reqs, reqs, 1.0, 0.2, None)
    assert win.failed == len(reqs)
    cap = 1.0 + 0.2
    assert win.latency_s() == pytest.approx([cap - r.at_s for r in reqs])
    r = harness.Readings(c, win, 1)
    assert harness.reader("out_tok_per_s")(r) == 0.0
    assert harness.reader("req_p90_ms")(r) > 0


def test_the_fp8_control_is_not_correct():
    """The control: the reference at fp8 in the program's place fails the
    cell's limits where the program passes them. Rounding error grows with
    depth, so this runs 12 layers (the cell runs 18)."""
    c = smoke.cell(layers=12, d_model=128)
    res, _, _ = smoke.run(c, seed=9, control="fp8")
    assert res["correct"] is True
    assert harness.check.verdict(res["control"], c.limits) is False
