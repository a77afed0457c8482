"""A family the harness does not know, found by name in a directory of its
own, runs a cell on the CPU through `harness.run_cell` with no edit to the
harness: its heads are wider than d_model / n_heads, and its attention
sizes come from a nested section that only its `model_config` reads."""
import dataclasses
from pathlib import Path

import pytest

from perfbench import harness
from perfbench.tests import smoke
from perfbench.tests.test_harness import _broken
from perfbench.tests.test_spec import served_layout

HERE = Path(__file__).resolve().parent
CFG = {"name": "nested-attention", "family": "nested_attention",
       "n_layers": 2, "d_model": 64, "d_ff": 160, "vocab_size": 512,
       "norm_eps": 1e-5, "tie_embeddings": False, "qkv_bias": False,
       "param_dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
       "kv_replication": 1,
       "attention": {"n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
                     "rope_theta": 10000.0}}


def fixture_cell() -> harness.Cell:
    return dataclasses.replace(
        smoke.cell(), cfg=CFG,
        family=harness.family(CFG["family"], root=HERE))


def test_the_nested_section_reaches_the_program():
    fam = fixture_cell().family
    mc = fam.model_config(CFG)
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim) == (4, 2, 32)
    assert mc.n_heads * mc.head_dim != mc.d_model
    ours, served = served_layout(fam, CFG)
    assert ours == served


def test_a_new_family_runs_a_cell_and_is_correct():
    res, checks, win = smoke.run(fixture_cell(), seed=2**31 + 61)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] == len(win.done)
    assert all(v <= lim for v, lim in checks.values())


@pytest.mark.parametrize("fault", ["unchanged_state", "altered_token"])
def test_a_new_family_with_a_broken_decode_is_not_correct(monkeypatch,
                                                          fault):
    _broken(monkeypatch, fault)
    res, checks, _ = smoke.run(fixture_cell(), seed=2**31 + 63)
    assert res["correct"] is False
    assert any(v > lim for v, lim in checks.values())


def test_an_unknown_family_names_the_file_it_looked_for():
    with pytest.raises(FileNotFoundError,
                       match=r"families/no_such_family\.py"):
        harness.family("no_such_family")
    with pytest.raises(FileNotFoundError, match=str(HERE / "families")):
        harness.family("dense", root=HERE)
