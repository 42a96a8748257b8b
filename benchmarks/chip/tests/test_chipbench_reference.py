"""The reference contract that lets layers differ: a reference's scales
(``embedding_multiplier``, ``logits_multiplier``), ``attention``'s ``scale``
and ``residual`` without positions (``theta=None``), per-leaf draw rules, and
the error for a leaf that no rule covers; each against a pass written out by
hand in float64."""
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import reference as R, weights as W  # noqa: E402

CFG = {"hidden_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 4, "vocab_size": 32, "rms_norm_eps": 1e-5,
       "tie_word_embeddings": True, "torch_dtype": "float32"}
D_ = {"D": 16, "H": 4, "KV": 2, "hd": 4}
SCALE, RESIDUAL, EMBED_X, LOGITS_X = 1 / 128, 0.22, 12.0, 1 / 16
# one leaf that is neither a norm scale nor a projection, drawn by its rule
GAIN = {"gain": lambda key, shape: jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)}


def _shapes(cfg, kind):
    return {**R.attention_shapes(D_), "gain": (16,)}


# a one-layer model with muP scales and a NoPE attention block
TOY = SimpleNamespace(
    dims=lambda cfg: dict(D_, L=1),
    layer_shapes=_shapes,
    layer_weights=lambda key, li, cfg, kind, dtype: W.layer_draw(
        key, li, _shapes(cfg, kind), dtype, GAIN),
    layer=lambda h, w, cfg, kind, lower=None: R.attention(
        h * w["gain"], w, D_, 1e-5, None, lower, scale=SCALE, residual=RESIDUAL),
    embedding_multiplier=lambda cfg: EMBED_X,
    logits_multiplier=lambda cfg: LOGITS_X)


def _rms(x, w, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _by_hand(seq, key, rows):
    embed, head, norm = (np.asarray(a, np.float64)
                         for a in W.embed_weights(key, CFG, rows, jnp.float32))
    w = {n: np.asarray(a, np.float64) for n, a in
         jax.jit(lambda k: TOY.layer_weights(k, 0, CFG, W.ONE_KIND, jnp.float32))(key).items()}
    h = embed[seq] * EMBED_X
    x = _rms(h * w["gain"], w["ln1"])
    s = len(seq)
    q = (x @ w["wq"]).reshape(s, 4, 4)
    k = np.repeat((x @ w["wk"]).reshape(s, 2, 4), 2, axis=1)
    v = np.repeat((x @ w["wv"]).reshape(s, 2, 4), 2, axis=1)
    sc = np.einsum("qhd,khd->hqk", q, k) * SCALE
    sc = np.where(np.tril(np.ones((s, s), bool))[None], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", p, v).reshape(s, 16)
    h = h * w["gain"] + RESIDUAL * (o @ w["wo"])
    return (_rms(h, norm) @ head)[:, :32] * LOGITS_X


def test_scales_and_nope_attention_by_hand():
    seed, rows = 2**32 + 3, 64
    seq = [5, 1, 30, 7, 7, 2, 19]
    got = R.Layerwise(TOY, CFG, seed, rows, 512).logits([seq], [list(range(len(seq)))])[0]
    want = _by_hand(seq, W.root_key(seed), rows)
    # float32 products at ``highest`` against float64: rounding of a few
    # sums of 16 terms
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_a_rule_draws_its_leaf_under_the_key_scheme():
    # each draw jitted with the key an argument, as both sides make them
    key = W.root_key(7)
    w = jax.jit(lambda k: W.layer_draw(k, 3, _shapes(CFG, W.ONE_KIND), jnp.float32, GAIN))(key)
    own = jax.jit(lambda k: GAIN["gain"](
        jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(k, 1), 3), 6), (16,)))(key)
    np.testing.assert_array_equal(np.asarray(w["gain"]), np.asarray(own))
    # the other leaves as without the rule
    plain = jax.jit(lambda k: W.layer_draw(k, 3, R.attention_shapes(D_), jnp.float32))(key)
    for n in plain:
        np.testing.assert_array_equal(np.asarray(w[n]), np.asarray(plain[n]))


def test_mamba2_rules_draw_the_published_ranges():
    d = {"D": 16, "ssm_heads": 64, "ssm_head_dim": 4, "ssm_state": 8, "ssm_groups": 1,
         "conv": 4}
    w = W.layer_draw(W.root_key(11), 0, R.mamba2_shapes(d), jnp.float32, R.mamba2_rules(d))
    a = np.exp(np.asarray(w["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(np.asarray(w["dt_bias"], np.float64)))
    assert (dt >= R.DT_MIN * (1 - 1e-5)).all() and (dt <= R.DT_MAX * (1 + 1e-5)).all()
    np.testing.assert_array_equal(np.asarray(w["D"]), 1.0)
    for n in ("conv_x_b", "conv_B_b", "conv_C_b"):
        b = np.asarray(w[n])
        assert (np.abs(b) <= 0.5).all() and np.abs(b).max() > 0.1
    # the depthwise kernel's fan-in is its width
    assert np.std(np.asarray(w["conv_x_w"])) == pytest.approx(0.5, rel=0.2)


def test_a_one_axis_leaf_no_rule_covers_is_an_error():
    with pytest.raises(ValueError, match="A_log"):
        W.layer_draw(W.root_key(0), 0, {"A_log": (8,)})
