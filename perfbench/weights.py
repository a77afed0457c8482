"""Seeded random weights, made on the device in one jitted call, in the
dtype they are served in. A configuration's family (`families/<family>.py`)
draws its own tree with `make_params`; the helpers here are shared."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding


def key32(seed: int) -> int:
    """Any whole seed, however large, as 32 bits for the device's RNG."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def padded_vocab(cfg: Dict[str, Any]) -> int:
    return -(-int(cfg["vocab_size"]) // 256) * 256


def _uniform(key, shape, std, dtype):
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def init_on_device(family, cfg: Dict[str, Any], seed: int, device):
    """The weights of `seed`, drawn by `family.make_params`, committed to
    `device`."""
    fn = jax.jit(lambda k: family.make_params(k, cfg),
                 out_shardings=SingleDeviceSharding(device))
    return fn(jax.random.key(key32(seed), impl="rbg"))
