"""The comparison that decides `correct`.

After the window, a sample of the finished requests, drawn from the seed
and always holding the one with the most served tokens and the one with
the longest prompt, is run through the plain float32 reference once each:
the prompt followed by the served tokens. At every position where a token
was served (the first from prefill, the rest from decode steps at every
depth into the cache) the gap is how far the served token's reference
logit lies below the reference's best, in units of that position's logit
standard deviation, so one limit reads the same at any width. Greedy
serving of the bfloat16 model puts a near-tie first now and then, which
reads a small gap; a wrong cache entry, position or token reads several
standard deviations.

The control puts the reference at fp8 (`reference.logits_at(quant=)`) in
the program's place and reads, at the same positions, the gap of the
token that it puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference

MIN_BUCKET = 256


def choose_sample(finished: Sequence[Any], seed: int, spec: Dict[str, int]
                  ) -> List[Any]:
    """Requests to check: the longest answer, the longest prompt, then
    seeded picks until `min_tokens` served tokens and `min_requests`
    requests are in, at most `max_requests`."""
    if not finished:
        return []
    by_id = sorted(finished, key=lambda r: r.id)
    first = [max(by_id, key=lambda r: len(r.output)),
             max(by_id, key=lambda r: len(r.prompt))]
    picked = list({id(r): r for r in first}.values())
    rest = [r for r in by_id if all(r is not p for p in picked)]
    order = np.random.default_rng(seed).permutation(len(rest))
    for i in order:
        if len(picked) >= spec["max_requests"] or (
                len(picked) >= spec["min_requests"]
                and sum(len(r.output) for r in picked) >= spec["min_tokens"]):
            break
        picked.append(rest[i])
    return picked


def bucket(n: int, cap: int) -> int:
    """Sequence length padded to a power of two (at least MIN_BUCKET, at
    most `cap`), so the reference compiles a few shapes only."""
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return min(max(b, n), max(cap, n))


@jax.jit
def _gaps(ref, picked):
    """ref (P, V) logits, picked (P,) ids -> (P,) gaps in logit stds."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref, axis=-1)


def served_gaps(params, cfg: Dict[str, Any], reqs: Sequence[Any],
                max_len: int, out_max: int,
                control: Optional[str] = None) -> Dict[str, List[float]]:
    """Per served token, the gap of the served token ("program") and,
    with `control`, of the control's first choice ("control")."""
    dims = reference.Dims.of(cfg)
    out: Dict[str, List[float]] = {"program": [], "control": []}
    for r in reqs:
        n, m = len(r.prompt), len(r.output)
        seq = list(r.prompt) + list(r.output[:-1])
        tokens = np.zeros((bucket(len(seq), max_len),), np.int32)
        tokens[:len(seq)] = seq
        at = np.full((out_max,), n - 1 + m - 1, np.int32)
        at[:m] = np.arange(n - 1, n - 1 + m)
        served = np.zeros((out_max,), np.int32)
        served[:m] = r.output
        ref = reference.logits_at(params, jnp.asarray(tokens),
                                  jnp.asarray(at), dims=dims)
        out["program"] += np.asarray(_gaps(ref, jnp.asarray(served)))[:m] \
            .tolist()
        if control:
            low = reference.logits_at(params, jnp.asarray(tokens),
                                      jnp.asarray(at), dims=dims,
                                      quant=control)
            first = jnp.argmax(low, axis=-1).astype(jnp.int32)
            out["control"] += np.asarray(_gaps(ref, first))[:m].tolist()
        del ref
    return out


def numbers(gaps: List[float], short_outputs: int) -> Dict[str, float]:
    """The numbers compared, by name."""
    g = np.asarray(gaps, np.float64)
    return {"gap_max": float(g.max()) if g.size else float("nan"),
            "gap_mean": float(g.mean()) if g.size else float("nan"),
            "short_outputs": float(short_outputs)}


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit (a NaN, nothing checked, fails)."""
    return all(k in nums and nums[k] <= lim for k, lim in limits.items())
