"""The engine's in-place decode against the masked, copying decode it replaced.

The reference below is the earlier decode program, kept here as the oracle:
its layer loop takes each layer's cache as scan ``xs`` and gives it back
whole as ``ys``, and the engine then selects every cache leaf, old or new, by
the active mask.  The engine under test carries the stacked cache through
the loop, writes one row per slot in place, masks only ``lengths`` and
donates the cache.  Both run one schedule of adds, steps, a preemption, a
promotion, a slot that ends and a freed slot refilled mid-stream; the tokens
they emit must be the same, token for token.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import attention as A
from repro.models import mlp as mlp_mod
from repro.models import model as M
from repro.models.common import apply_rope, rms_norm
from repro.serving.engine import ServingEngine
from repro.training import steps

MAX_LEN = 24
BATCH = 4
_BASE = dataclasses.replace(get_config("granite-8b").reduced(), dtype="float32")
CONFIGS = {
    "dense_f32": _BASE,
    "int8_kv": dataclasses.replace(_BASE, kv_cache_dtype="int8"),
    # smax = 8 <= window: a rolling buffer the prompts overrun
    "rolling_window": dataclasses.replace(_BASE, sliding_window=8),
}


def _reference_attn(p, x, k_cache, v_cache, lengths, cfg):
    """The earlier one-layer ``attn_decode``: the new row scattered into a
    copy of the layer's cache (B,Smax,KV,hd), or its int8 pair."""
    b = x.shape[0]
    quant = cfg.kv_cache_dtype == "int8"
    smax = (k_cache[0] if quant else k_cache).shape[1]
    q, k, v = A._qkv(p, x, cfg)
    q = apply_rope(q, lengths[:, None], cfg.rope_theta)
    k = apply_rope(k, lengths[:, None], cfg.rope_theta)
    rolling = cfg.sliding_window is not None and smax <= cfg.sliding_window
    slot = lengths % smax if rolling else jnp.minimum(lengths, smax - 1)
    bidx = jnp.arange(b)
    if quant:
        (kq, ks), (vq, vs) = k_cache, v_cache
        knq, kns = A.kv_quantize(k[:, 0])
        vnq, vns = A.kv_quantize(v[:, 0])
        k_cache = (kq.at[bidx, slot].set(knq), ks.at[bidx, slot].set(kns))
        v_cache = (vq.at[bidx, slot].set(vnq), vs.at[bidx, slot].set(vns))
        k_att = A.kv_dequantize(*k_cache, x.dtype)
        v_att = A.kv_dequantize(*v_cache, x.dtype)
    else:
        k_cache = k_att = k_cache.at[bidx, slot].set(k[:, 0])
        v_cache = v_att = v_cache.at[bidx, slot].set(v[:, 0])
    if rolling:
        o = ops.decode_attention(q[:, 0], k_att, v_att, jnp.minimum(lengths + 1, smax),
                                 impl="jnp")
    else:
        o = ops.decode_attention(q[:, 0], k_att, v_att, lengths + 1,
                                 window=cfg.sliding_window, impl="jnp")
    return o.reshape(b, 1, -1) @ p["wo"], k_cache, v_cache


def _reference_step(params, cfg, cache, tokens):
    """The earlier dense ``decode_step``: each layer's cache in and out of
    the layer loop whole."""
    lengths = cache["lengths"]
    h = params["embed"][tokens][:, None, :]

    def body(h, xs):
        blk, kc, vc = xs
        o, kc, vc = _reference_attn(blk["attn"], rms_norm(h, blk["ln1"], cfg.norm_eps),
                                    kc, vc, lengths, cfg)
        h = h + o
        h = h + mlp_mod.mlp_forward(blk["mlp"], rms_norm(h, blk["ln2"], cfg.norm_eps))
        return h, (kc, vc)

    h, (kc, vc) = jax.lax.scan(body, h, (params["blocks"], cache["k"], cache["v"]))
    return M.lm_logits(params, cfg, h[:, 0]), {"lengths": lengths + 1, "k": kc, "v": vc}


def _reference_decode(cfg):
    """The earlier engine ``_decode``: every cache leaf masked, no donation."""

    @jax.jit
    def _decode(params, cache, tokens, active_mask):
        logits, new_cache = _reference_step(params, cfg, cache, tokens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def mask(new, old):
            # the batch axis: 0 for lengths, 1 for every (L, B, ...) leaf
            shape = [1] * new.ndim
            shape[0 if new.ndim == 1 else 1] = active_mask.shape[0]
            return jnp.where(active_mask.reshape(shape), new, old)
        return nxt, logits, jax.tree.map(mask, new_cache, cache)

    return _decode


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def setup(request):
    cfg = CONFIGS[request.param]
    return cfg, M.init_params(jax.random.key(3), cfg)


def _run_schedule(engine):
    """Adds, steps, preempt, promote, a slot ending at max_len, and its
    slot refilled mid-stream.  Returns every step's emitted tokens and the
    ``lengths`` seen where a slot sat inactive."""
    rng = np.random.default_rng(7)

    def prompt(n):
        return rng.integers(2, engine.cfg.vocab_size, n).tolist()

    emitted, held = [], []

    def step():
        before = np.asarray(engine.cache["lengths"])
        out = engine.step()
        after = np.asarray(engine.cache["lengths"])
        idle = [s.idx for s in engine.slots if s.idx not in out]
        held.append((before[idle].tolist(), after[idle].tolist()))
        emitted.append(sorted(out.items()))

    a = engine.add_request(prompt(12), request_id=1)
    s1 = engine.add_request(prompt(9), request_id=-1, speculative=True)
    s2 = engine.add_request(prompt(10), request_id=-2, speculative=True)
    for _ in range(3):
        step()
    engine.preempt(s1)
    engine.promote(s2, request_id=2)
    for _ in range(2):
        step()
    b = engine.add_request(prompt(5), request_id=3)          # into the freed slot
    assert b == s1
    while engine.slots[a].active:                           # `a` runs to max_len
        step()
    c = engine.add_request(prompt(14), request_id=4)         # refill mid-stream
    assert c == a
    for _ in range(4):
        step()
    return emitted, held


def test_engine_emits_the_tokens_of_the_masked_copying_decode(setup):
    cfg, params = setup
    got = ServingEngine(cfg, params, max_batch=BATCH, max_len=MAX_LEN, eos_id=-1)
    want = ServingEngine(cfg, params, max_batch=BATCH, max_len=MAX_LEN, eos_id=-1)
    want._decode = _reference_decode(cfg)
    got_tokens, got_held = _run_schedule(got)
    want_tokens, _ = _run_schedule(want)
    assert got_tokens == want_tokens
    # an inactive slot's lengths does not advance
    assert got_held and all(before == after for before, after in got_held)
    assert any(before for before, _ in got_held)


def test_serve_step_gives_the_logits_of_the_copying_decode_on_a_full_batch(setup):
    cfg, params = setup
    toks = jax.random.randint(jax.random.key(4), (BATCH, 11), 2, cfg.vocab_size)
    logits, cache = M.prefill(params, cfg, {"tokens": toks}, MAX_LEN)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    want_logits, want_cache = jax.jit(
        lambda p, c, t: _reference_step(p, cfg, c, t))(params, cache, tok)
    nxt, got_logits, got_cache = jax.jit(
        lambda p, c, t: steps.serve_step(p, c, t, cfg=cfg))(params, cache, tok)
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(want_logits),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(jnp.argmax(want_logits, -1)))
    for g, w in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
