import collections
import json

import numpy as np
import pytest

from perfbench import traffic
from perfbench.harness import BENCH

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 20.0, 2**33 + 5, 1000)
    b = traffic.generate(mix(name), 20.0, 2**33 + 5, 1000)
    assert [(r.at_s, r.prompt, r.max_new) for r in a] == \
        [(r.at_s, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_schedule_not_the_tokens(name):
    m = mix(name)
    a = traffic.generate(m, 30.0, 1, 1000)
    b = traffic.generate(m, 30.0, 2, 1000)
    assert len(a) == len(b) == round(m["arrivals"]["rate_per_s"] * 30)
    assert [(r.at_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.at_s, len(r.prompt), r.max_new) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    # the schedule is a shuffled draw, not sorted by size
    outs = [r.max_new for r in a]
    assert outs != sorted(outs) and outs != sorted(outs, reverse=True)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix(name):
    m = mix(name)
    reqs = traffic.generate(m, 200.0, 3, 1000)
    n = len(reqs)
    counts = collections.Counter(len(r.prompt) for r in reqs)
    w = np.asarray(m["prompt_len"]["weights"], float)
    for length, share in zip(m["prompt_len"]["support"], w / w.sum()):
        assert abs(counts[length] - share * n) <= 1
    outs = np.asarray([r.max_new for r in reqs])
    o = m["output_len"]
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    assert abs(np.median(outs) - o["median"]) <= 1
    at = np.asarray([r.at_s for r in reqs])
    assert at[0] == 0.0 and np.all(np.diff(at) >= 0) and at[-1] < 200.0
    assert all(0 <= t < 1000 for r in reqs[:20] for t in r.prompt)
    assert max(m["prompt_len"]["support"]) + o["max"] <= m["engine"]["max_len"]


def test_poisson_gaps_are_exponential_quantiles():
    m = {"arrivals": {"process": "poisson", "rate_per_s": 1.0}}
    g = np.asarray(traffic.gaps(m, 1000))
    assert abs(g.mean() - 1.0) < 0.01 and abs(np.median(g) - np.log(2)) < 0.01


def test_gamma_arrivals_are_bursty():
    m = {"arrivals": {"process": "gamma", "rate_per_s": 1.0, "cv": 3.0}}
    g = np.asarray(traffic.gaps(m, 4000))
    assert 2.5 < g.std() / g.mean() < 3.5
    assert traffic.gaps(m, 50) == traffic.gaps(m, 50)


def test_unknown_process_refused():
    with pytest.raises(ValueError):
        traffic.gaps({"arrivals": {"process": "uniform"}}, 4)
