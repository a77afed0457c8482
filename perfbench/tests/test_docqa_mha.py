"""The deepseek-llm-7b.docqa cell's own files at a width a CPU test can
hold: full multi-head attention (as many KV heads as query heads) at the
mix's real long-document lengths, so prefill runs several query blocks and
decode reads several key blocks. Driven through serve_fleet and compared
with the plain reference, and the faults the comparison has to catch.

Answers run 128-200 tokens here, longer than the mix's 16-96, so that the
cache a decode step writes is a few percent of what it reads. At the mix's
own answers, random weights spread attention so evenly over 2-4k keys that
a decode step which drops its own cache writes moves the served tokens
little at this width: gap_max about 0.4 against the cell's limit of 0.35,
too close to test on, where these answers give about 1.9."""
import copy
import time

import pytest

from perfbench import harness
from perfbench.tests.test_harness import _broken

CELL = "deepseek-llm-7b.docqa"


def mha_cell() -> harness.Cell:
    real = harness.load_cell(CELL)
    cfg = dict(real.cfg, n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
               d_ff=160, vocab_size=512, head_dim=16)
    mix = copy.deepcopy(real.mix)
    mix["arrivals"]["rate_per_s"] = 1.0
    mix["output_len"].update(median=160, sigma=0.2, min=128, max=200)
    mix["engine"]["slots"] = 2
    mix["check"].update(min_requests=3, max_requests=3, min_tokens=300)
    return harness.Cell(real.name, cfg, mix, 1, real.limits, real.end_to_end,
                        real.per_layer, real.family)


def run(seed: int):
    return harness.run_cell(mha_cell(), seed, 3.0, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            log=lambda _m: None)


def test_cell_keeps_the_long_lengths_and_full_multi_head_attention():
    c = mha_cell()
    assert c.cfg["n_kv_heads"] == c.cfg["n_heads"]
    assert harness.load_cell(CELL).cfg["n_kv_heads"] == 32
    assert min(c.mix["prompt_len"]["support"]) == 2048
    assert c.mix["engine"]["max_len"] == 4096


def test_served_mha_path_matches_the_reference():
    res, checks, win = run(seed=2**31 + 41)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] == len(win.done) == 3
    assert checks["gap_max"][0] <= checks["gap_max"][1]


@pytest.mark.parametrize("fault", ["unchanged_state", "altered_token"])
def test_a_broken_mha_path_is_not_correct(monkeypatch, fault):
    _broken(monkeypatch, fault)
    res, checks, _ = run(seed=2**31 + 43)
    assert res["correct"] is False
    assert any(v > lim for v, lim in checks.values())
