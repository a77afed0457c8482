"""BENCHMARK.json and the files it names: every configuration, mix, limit
and metric is found by name, and the configurations are what they say."""
import dataclasses
import json
import re

import jax
import pytest

from perfbench import harness
from perfbench.harness import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["perfbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_every_metric_has_a_reader_and_its_links_hold():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert 0.0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert callable(harness.reader(m["name"]))
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        if m["unit"] == "%" and m["name"].endswith("_roofline"):
            assert m["source"] == "device_trace"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4)
    assert {"gap_max", "gap_mean", "short_outputs"} <= set(c.limits)
    assert c.per_layer and any(m["name"] == "setup_s" for m in c.end_to_end)
    c.family.model_config(c.cfg)


def served_layout(fam, cfg):
    """The family's weights and the served model's, as trees of shapes."""
    from repro.models import build_model
    ours = jax.eval_shape(lambda k: fam.make_params(k, cfg),
                          jax.random.key(0, impl="rbg"))
    served = jax.eval_shape(build_model(fam.model_config(cfg)).init_params,
                            jax.random.PRNGKey(0))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    return shape(ours), shape(served)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_files(entry):
    """Every configuration names its family, and the family's weights
    have the served layout, whatever the family."""
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert set(cfg["published"]) >= set(entry["reduced"])
    ours, served = served_layout(harness.family(cfg["family"]), cfg)
    assert ours == served


@pytest.mark.parametrize("cell", CELLS)
def test_weights_have_the_served_layout(cell):
    """The family a cell loads with draws the tree its program serves."""
    c = harness.load_cell(cell)
    ours, served = served_layout(c.family, c.cfg)
    assert ours == served


def test_granite_is_the_registry_model_cut_to_one_stage():
    from repro.configs import get_config
    c = harness.load_cell("granite-8b.chat")
    want = get_config("granite-8b").replace(n_layers=18, kv_replication=1,
                                            head_dim=128)
    assert c.family.model_config(c.cfg) == want
    assert get_config("granite-8b").n_layers == c.cfg["published"]["n_layers"]


def test_peaks_table_is_keyed_by_device_kind():
    table = json.loads((BENCH / "peaks.json").read_text())
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    r = harness.Readings(harness.load_cell(CELLS[0]), None, 1, kind="cpu")
    with pytest.raises(KeyError):
        r.peaks()


def test_readings_fields_are_what_readers_use():
    fields = {f.name for f in dataclasses.fields(harness.Readings)}
    assert {"window", "probes", "trace", "lo", "hi", "chips", "cell"} <= fields
