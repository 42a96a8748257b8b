"""The served weights and the reference's are one draw: the stacked tree made
in one jitted call holds, layer by layer, what the reference regenerates."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "src"))

from chipbench import system, testing, weights  # noqa: E402


@pytest.mark.parametrize("tie", [False, True])
def test_served_tree_matches_the_per_layer_draw(tie):
    cfg = dict(testing.TINY_CONFIG, tie_word_embeddings=tie)
    mcfg = system.model_config(cfg)
    seed = 2**33 + 9
    p = system.served_params(cfg, mcfg, seed)
    key = weights.root_key(seed)
    for li in range(cfg["num_hidden_layers"]):
        w = weights.layer_weights(key, li, cfg)
        got = {"ln1": p["blocks"]["ln1"], "ln2": p["blocks"]["ln2"],
               **p["blocks"]["attn"], **p["blocks"]["mlp"]}
        for n in weights.LAYER_LEAVES:
            np.testing.assert_array_equal(np.asarray(got[n][li]), np.asarray(w[n]))
    embed, head, norm = weights.embed_weights(key, cfg, mcfg.padded_vocab)
    np.testing.assert_array_equal(np.asarray(p["embed"]), np.asarray(embed))
    np.testing.assert_array_equal(np.asarray(p["lm_head"]), np.asarray(head))
    np.testing.assert_array_equal(np.asarray(p["final_norm"]), np.asarray(norm))
    assert np.array_equal(np.asarray(head), np.asarray(embed).T) == tie


def test_seeds_above_32_bits_differ():
    a = jax.random.key_data(weights.root_key(5))
    b = jax.random.key_data(weights.root_key(2**32 + 5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
