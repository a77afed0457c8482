"""Seeded open-loop traffic from a mix's parameters (`traffic/<mix>.json`).

Every seed gets the same schedule (prompt lengths, output budgets and
send times, drawn once for the mix from a fixed generator) and its own
prompt token ids. Served greedily with no end token, the ids change what
is computed but not how much, so the spread between seeds is the
system's, not the draw's. (With the order drawn per seed instead, which
long answers fall late enough to miss the window moved the chat cell's
`out_tok_per_s` by a fifth from seed to seed.)

- Prompt lengths: the fixed support, each value taken by its share of the
  requests (largest remainder).
- Output budgets: log-normal quantiles at (i + 0.5) / n, rounded to whole
  tokens and clipped to [min, max].
- Arrivals: `poisson` uses exponential quantiles for the gaps; `gamma`
  (a renewal process with coefficient of variation `cv`, BurstGPT-style
  bursts) uses gaps drawn once from a fixed generator. The gaps are scaled
  so that n requests are due in [0, seconds).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

_SCHEDULE_SEED = 20240917    # the mix's one schedule, the same for every run


@dataclass
class Req:
    id: int
    at_s: float              # scheduled send, seconds after the window opens
    prompt: List[int]
    max_new: int
    output: List[int] = field(default_factory=list)


def n_requests(mix: Dict[str, Any], seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))


def _shares(weights: List[float], n: int) -> List[int]:
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def prompt_lengths(mix: Dict[str, Any], n: int) -> List[int]:
    p = mix["prompt_len"]
    out: List[int] = []
    for length, c in zip(p["support"], _shares(p["weights"], n)):
        out += [int(length)] * c
    return out


def output_budgets(mix: Dict[str, Any], n: int) -> List[int]:
    o = mix["output_len"]
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [int(min(o["max"], max(o["min"], round(o["median"]
                                                  * math.exp(o["sigma"] * v)))))
            for v in z]


def gaps(mix: Dict[str, Any], n: int) -> List[float]:
    a = mix["arrivals"]
    if a["process"] == "poisson":
        return [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    if a["process"] == "gamma":
        shape = 1.0 / float(a["cv"]) ** 2
        rng = np.random.default_rng(_SCHEDULE_SEED)
        return rng.gamma(shape, 1.0 / shape, n).tolist()
    raise ValueError(f"unknown arrival process {a['process']!r}")


def generate(mix: Dict[str, Any], seconds: float, seed: int,
             vocab: int) -> List[Req]:
    """The run's requests, in order of their scheduled send."""
    n = n_requests(mix, seconds)
    max_len = mix["engine"]["max_len"]
    if max(mix["prompt_len"]["support"]) + mix["output_len"]["max"] > max_len:
        raise ValueError("longest prompt plus longest answer exceeds max_len")
    order = np.random.default_rng(_SCHEDULE_SEED)
    lens = order.permutation(prompt_lengths(mix, n))
    outs = order.permutation(output_budgets(mix, n))
    g = order.permutation(gaps(mix, n))
    rng = np.random.default_rng(seed)
    starts = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    starts = starts * (seconds / float(np.sum(g)))
    return [Req(id=i, at_s=float(starts[i]),
                prompt=rng.integers(0, vocab, int(lens[i])).tolist(),
                max_new=int(outs[i]))
            for i in range(n)]
