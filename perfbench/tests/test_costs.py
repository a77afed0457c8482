"""The flop and byte counts against hand computations for both configs."""
import json

import jax
import pytest

from perfbench import costs, weights
from perfbench.harness import BENCH


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_granite_by_hand():
    c = cfg("granite-8b")
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, SwiGLU 3x4096x14336
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert costs.layer_matmul_params(c) == layer == 218_103_808
    # 8 KV heads x 128 x (K, V) x 18 layers x 2 bytes = 73,728 B per token
    assert costs.kv_bytes_per_token(c) == 73_728
    weights_b = (18 * (layer + 2 * 4096) + 49152 * 4096 + 4096) * 2
    assert costs.decode_step_bytes(c, []) == weights_b
    assert costs.decode_step_bytes(c, [100, 300]) == \
        weights_b + 2 * 4096 * 2 + (400 + 2) * 73_728
    assert costs.decode_flops(c, [100]) == \
        2 * (18 * layer + 4096 * 49152) + 4 * 18 * 32 * 128 * 100
    assert costs.prefill_flops(c, 4) == \
        2 * 4 * 18 * layer + 2 * 4096 * 49152 + 4 * 18 * 32 * 128 * 10


def test_deepseek_by_hand():
    c = cfg("deepseek-llm-7b")
    # full multi-head attention: q, k, v, o all 4096x4096; SwiGLU 3x4096x11008
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert costs.layer_matmul_params(c) == layer == 202_375_168
    # 32 KV heads x 128 x 2 x 10 layers x 2 bytes = 163,840 B per token
    assert costs.kv_bytes_per_token(c) == 163_840
    assert costs.decode_step_bytes(c, [4096]) == \
        (10 * (layer + 2 * 4096) + 102400 * 4096 + 4096) * 2 \
        + 4096 * 2 + 4097 * 163_840
    assert costs.prefill_flops(c, 2048) == \
        2 * 2048 * 10 * layer + 2 * 4096 * 102400 \
        + 4 * 10 * 32 * 128 * (2048 * 2049 // 2)


@pytest.mark.parametrize("name", ["granite-8b", "deepseek-llm-7b"])
def test_weights_hold_what_the_costs_count(name):
    c = cfg(name)
    tree = jax.eval_shape(lambda k: weights.make_params(k, c),
                          jax.random.key(0, impl="rbg"))
    n = sum(a.size for a in jax.tree.leaves(tree))
    d = c["d_model"]
    tok = weights.padded_vocab(c) * d
    assert n == c["n_layers"] * (costs.layer_matmul_params(c) + 2 * d) \
        + tok + costs.head_params(c) + d
