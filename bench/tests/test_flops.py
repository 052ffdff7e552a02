"""The FLOP count ``train_mfu`` divides by, against hand-computed numbers
for both configurations."""
import json
import os

import pytest

from spec import BENCH, load_module

dense = load_module(os.path.join(BENCH, "families", "dense.py"), "dense_t")


# Qwen3-4B's published keys (huggingface.co/Qwen/Qwen3-4B, config.json)
# at 10 of its 36 layers: a second family through the same count, kept
# here until a cell runs it (PERF.md, section 7).
QWEN3_4B_D10 = {
    "model_type": "qwen3", "hidden_size": 2560, "intermediate_size": 9728,
    "num_hidden_layers": 10, "num_attention_heads": 32,
    "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000, "attention_bias": False,
    "tie_word_embeddings": True}


def published(name):
    if name == "qwen3-4b-d10":
        return QWEN3_4B_D10
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["config"]


# (config, non-embedding matmul weights, layers, heads)
CASES = [("qwen2-1.5b", 1_310_195_712, 28, 12),
         ("qwen3-4b-d10", 1_009_254_400, 10, 32)]


@pytest.mark.parametrize("name,matmul,L,H", CASES)
def test_forward_flops_by_hand(name, matmul, L, H):
    c = published(name)
    S = 256
    attn = L * 4 * H * 128 * (S + 1) / 2  # per token, mean keys (S+1)/2
    head = 2 * c["hidden_size"] * 151_936  # the one position the loss reads
    assert dense.forward_flops(c, S) == S * (2 * matmul + attn) + head


@pytest.mark.parametrize("name,per_token_step", [("qwen2-1.5b", 5.28e9),
                                                 ("qwen3-4b-d10", 4.08e9)])
def test_flops_per_token_step(name, per_token_step):
    """Two forwards per ZO step: about 5.28 and 4.08 GFLOP per token."""
    f = 2 * dense.forward_flops(published(name), 256) / 256
    assert f == pytest.approx(per_token_step, rel=0.005)


@pytest.mark.parametrize("name", [n for n, *_ in CASES])
def test_weight_shapes_count(name):
    """The benchmark's layout holds the published parameter count."""
    import math

    import jax
    shapes = jax.tree.leaves(dense.weight_shapes(published(name)),
                             is_leaf=lambda x: isinstance(x, tuple))
    total = sum(math.prod(s) for s in shapes)
    assert total == {"qwen2-1.5b": 1_543_714_304,
                     "qwen3-4b-d10": 151_936 * 2560 + 2560
                     + 10 * (100_925_440 + 2 * 2560 + 2 * 128)}[name]
