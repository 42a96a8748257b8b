"""GQA attention block: init + train/prefill/decode application."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro import compat
from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.common import apply_rope, dense_init, split_keys


def init_attn_params(key, cfg: ModelConfig, dtype) -> dict:
    D, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    Hp, KVp = cfg.eff_n_heads, cfg.eff_n_kv_heads
    ks = split_keys(key, 4)

    def pad_cols(w, n_real, n_pad):
        if n_pad == n_real:
            return w
        return jnp.concatenate(
            [w, jnp.zeros((w.shape[0], (n_pad - n_real) * hd), w.dtype)], axis=1)

    wq = pad_cols(dense_init(ks[0], (D, H * hd), dtype=dtype), H, Hp)
    wk = pad_cols(dense_init(ks[1], (D, KV * hd), dtype=dtype), KV, KVp)
    wv = pad_cols(dense_init(ks[2], (D, KV * hd), dtype=dtype), KV, KVp)
    wo = dense_init(ks[3], (H * hd, D), in_axis=0, dtype=dtype)
    if Hp != H:
        # zero rows for padded heads: their (garbage) attention output never
        # reaches the residual stream, and their grads stay exactly zero —
        # the padded model is numerically identical to the unpadded one.
        wo = jnp.concatenate([wo, jnp.zeros(((Hp - H) * hd, D), wo.dtype)], axis=0)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hp * hd,), dtype)
        p["bk"] = jnp.zeros((KVp * hd,), dtype)
        p["bv"] = jnp.zeros((KVp * hd,), dtype)
    return p


def kv_quantize(k: jnp.ndarray):
    """Per-(…, head)-vector absmax int8 quantization over head_dim."""
    scale = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0].astype(jnp.float32)


def kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _qkv(p, x, cfg: ModelConfig):
    b, s, _ = x.shape
    H, KV, hd = cfg.eff_n_heads, cfg.eff_n_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (
        q.reshape(b, s, H, hd),
        k.reshape(b, s, KV, hd),
        v.reshape(b, s, KV, hd),
    )


def attn_forward(p, x, cfg: ModelConfig, *, impl: str) -> jnp.ndarray:
    """Full-sequence causal attention (training / prefill compute). x (B,S,D)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    pos = jnp.arange(s)[None, :]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = ops.flash_attention(q, k, v, causal=True, window=cfg.sliding_window, impl=impl)
    return o.reshape(b, s, -1) @ p["wo"]


def attn_prefill(p, x, cfg: ModelConfig, smax: int, *, impl: str):
    """Prefill: returns (out (B,S,D), k_cache, v_cache (B,Smax,KV,hd))."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    pos = jnp.arange(s)[None, :]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = ops.flash_attention(q, k, v, causal=True, window=cfg.sliding_window, impl=impl)
    out = o.reshape(b, s, -1) @ p["wo"]
    if cfg.sliding_window is not None and smax < s:
        # rolling buffer keeps only the last `smax` positions
        k, v = k[:, s - smax:], v[:, s - smax:]
        pad = 0
    else:
        pad = smax - s
    k_cache = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    v_cache = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if cfg.kv_cache_dtype == "int8":
        kq, ks = kv_quantize(k_cache)
        vq, vs = kv_quantize(v_cache)
        return out, (kq, ks), (vq, vs)
    return out, k_cache, v_cache


def _rolling(cfg: ModelConfig, smax: int) -> bool:
    return cfg.sliding_window is not None and smax <= cfg.sliding_window


def attn_decode(
    p, x, k_cache, v_cache, lengths, layer, cfg: ModelConfig, *, impl: str
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode of layer ``layer`` against the stacked cache.

    x (B,1,D); cache (L,B,Smax,KV,hd) — or, with an int8 cache, a (values
    int8, scales f32) pair of stacks; lengths (B,) = tokens already in cache;
    layer a scalar index into the stack.  The new K/V row of each sequence is
    written in place at ``[layer, b, slot_b]``; nothing else of the stack is
    rewritten.  Returns (out (B,1,D), new_k_cache, new_v_cache)."""
    b = x.shape[0]
    quant = cfg.kv_cache_dtype == "int8"
    smax = (k_cache[0] if quant else k_cache).shape[2]
    q, k, v = _qkv(p, x, cfg)                     # (B,1,H,hd)/(B,1,KV,hd)
    q = apply_rope(q, lengths[:, None], cfg.rope_theta)
    k = apply_rope(k, lengths[:, None], cfg.rope_theta)
    rolling = _rolling(cfg, smax)
    slot = lengths % smax if rolling else jnp.minimum(lengths, smax - 1)
    idx = (layer, jnp.arange(b), slot)
    if quant:
        (kq, ks), (vq, vs) = k_cache, v_cache
        knq, kns = kv_quantize(k[:, 0])
        vnq, vns = kv_quantize(v[:, 0])
        k_cache = (kq.at[idx].set(knq), ks.at[idx].set(kns))
        v_cache = (vq.at[idx].set(vnq), vs.at[idx].set(vns))
        # attention reads this layer dequantized
        k_att = kv_dequantize(k_cache[0][layer], k_cache[1][layer], x.dtype)
        v_att = kv_dequantize(v_cache[0][layer], v_cache[1][layer], x.dtype)
    else:
        k_cache = k_cache.at[idx].set(k[:, 0])
        v_cache = v_cache.at[idx].set(v[:, 0])
        # attention reads the layer out of the stack where it lies
        k_att, v_att = k_cache, v_cache
    if rolling:
        # cache holds a rotated window; decode attention masks by min(len+1, smax)
        # NOTE: positions are rotated; softmax is permutation-invariant so a
        # rotated cache is fine as long as RoPE was applied pre-insertion.
        o = ops.decode_attention(q[:, 0], k_att, v_att, jnp.minimum(lengths + 1, smax),
                                 impl=impl, layer=layer)
    else:
        o = ops.decode_attention(
            q[:, 0], k_att, v_att, lengths + 1, window=cfg.sliding_window, impl=impl,
            layer=layer,
        )
    return o.reshape(b, 1, -1) @ p["wo"], k_cache, v_cache


# ======================================================================
# Sharded split-KV flash-decode (perf path — EXPERIMENTS.md §Perf)
#
# For caches whose kv-head count does not divide the TP degree, the
# baseline shards the cache on the SEQUENCE axis and GSPMD then gathers
# the whole cache to every chip each step.  This path instead runs a
# distributed flash-decode inside shard_map: every model-shard attends
# over its local KV slice and only the per-head softmax partials
# (m, l, acc) cross the interconnect — psum bytes are O(B·H·hd), i.e.
# ~kilobytes instead of the gigabyte-scale cache.
# ======================================================================

def attn_decode_sharded(
    p, x, k_cache, v_cache, lengths, layer, cfg: ModelConfig, mesh_info,
):
    """Decode of layer ``layer`` with a sequence-sharded stacked KV cache.
    x (B,1,D); caches (L,B,Smax,KV,hd) sharded on axis 2 over `model`; each
    shard writes the new row in place if it holds the row's slot.  Returns
    (out (B,1,D), new_k_cache, new_v_cache)."""
    import jax
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    quant = cfg.kv_cache_dtype == "int8"
    if quant:
        (kq_c, ks_c), (vq_c, vs_c) = k_cache, v_cache
        smax = kq_c.shape[2]
    else:
        smax = k_cache.shape[2]
    KV, hd = cfg.eff_n_kv_heads, cfg.resolved_head_dim
    H = cfg.eff_n_heads
    grp = H // KV
    nm = mesh_info.n_model
    chunk = smax // nm
    scale = hd ** -0.5

    q, k, v = _qkv(p, x, cfg)                      # (B,1,H,hd)/(B,1,KV,hd)
    q = apply_rope(q, lengths[:, None], cfg.rope_theta)
    k = apply_rope(k, lengths[:, None], cfg.rope_theta)
    qg = (q[:, 0].astype(jnp.float32) * scale).reshape(b, KV, grp, hd)
    k_new, v_new = k[:, 0], v[:, 0]                # (B,KV,hd)

    rolling = _rolling(cfg, smax)
    slot = (lengths % smax) if rolling else jnp.minimum(lengths, smax - 1)
    eff_len = jnp.minimum(lengths + 1, smax) if rolling else lengths + 1

    shardable = b % mesh_info.n_batch == 0 and b >= mesh_info.n_batch
    b_ax = mesh_info.batch_axes if shardable else None
    ma = mesh_info.model_axis
    NEG = -1e30

    def body(qg_l, kn_l, vn_l, kc_l, vc_l, slot_l, eff_l, layer_l, *scales):
        rank = jax.lax.axis_index(ma)
        off = rank * chunk
        loc = jnp.clip(slot_l - off, 0, chunk - 1)
        in_rng = (slot_l >= off) & (slot_l < off + chunk)
        idx = (layer_l, jnp.arange(qg_l.shape[0]), loc)

        def put(c, new):
            keep = in_rng.reshape(in_rng.shape + (1,) * (new.ndim - 1))
            return c.at[idx].set(jnp.where(keep, new, c[idx]))

        kc_l, vc_l = put(kc_l, kn_l), put(vc_l, vn_l)
        if quant:
            ks_l, vs_l, kns_l, vns_l = scales
            ks_l, vs_l = put(ks_l, kns_l), put(vs_l, vns_l)
            k_eff = (kc_l[layer_l].astype(jnp.float32) * ks_l[layer_l][..., None]).astype(x.dtype)
            v_eff = (vc_l[layer_l].astype(jnp.float32) * vs_l[layer_l][..., None]).astype(x.dtype)
        else:
            k_eff = kc_l[layer_l]
            v_eff = vc_l[layer_l]
        # local partial flash-decode over this shard's cache slice — on TPU
        # this is the Pallas partials kernel (kernels/decode_attention.py),
        # on CPU the identical jnp path; only (m, l, acc) cross the ICI.
        eff_local = jnp.clip(eff_l - off, 0, chunk)
        win = cfg.sliding_window if (cfg.sliding_window is not None and not rolling) else None
        acc, m, l = ops.decode_attention_partials(
            qg_l.reshape(qg_l.shape[0], KV * grp, hd), k_eff, v_eff,
            eff_local, scale=1.0, window=win,
        )
        # combine softmax partials across shards (tiny psum)
        m_g = jax.lax.pmax(m, ma)
        coef = jnp.where(jnp.isfinite(m), jnp.exp(m - m_g), 0.0)
        l_g = jax.lax.psum(l * coef, ma)
        acc_g = jax.lax.psum(acc * coef[..., None], ma)
        o = (acc_g / jnp.maximum(l_g[..., None], 1e-30)).astype(x.dtype)
        if quant:
            return o, kc_l, vc_l, ks_l, vs_l
        return o, kc_l, vc_l

    in_specs = [
        P(b_ax, None, None, None),      # qg
        P(b_ax, None, None),            # k_new
        P(b_ax, None, None),            # v_new
        P(None, b_ax, ma, None, None),  # k_cache (seq-sharded)
        P(None, b_ax, ma, None, None),  # v_cache
        P(b_ax),                        # slot
        P(b_ax),                        # eff_len
        P(),                            # layer
    ]
    out_specs = [P(b_ax, None, None, None), P(None, b_ax, ma, None, None),
                 P(None, b_ax, ma, None, None)]
    if quant:
        knq, kns = kv_quantize(k_new)
        vnq, vns = kv_quantize(v_new)
        in_specs += [P(None, b_ax, ma, None), P(None, b_ax, ma, None),  # ks, vs caches
                     P(b_ax, None), P(b_ax, None)]                      # new scales
        out_specs += [P(None, b_ax, ma, None), P(None, b_ax, ma, None)]
        fn = compat.shard_map(body, mesh=mesh_info.mesh, in_specs=tuple(in_specs),
                              out_specs=tuple(out_specs))
        o, kq_c, vq_c, ks_c, vs_c = fn(qg, knq, vnq, kq_c, vq_c, slot, eff_len, layer,
                                       ks_c, vs_c, kns, vns)
        out = o.reshape(b, 1, H * hd) @ p["wo"]
        return out, (kq_c, ks_c), (vq_c, vs_c)
    fn = compat.shard_map(body, mesh=mesh_info.mesh, in_specs=tuple(in_specs),
                          out_specs=tuple(out_specs))
    o, k_cache, v_cache = fn(qg, k_new, v_new, k_cache, v_cache, slot, eff_len, layer)
    out = o.reshape(b, 1, H * hd) @ p["wo"]
    return out, k_cache, v_cache


def attn_decode_dispatch(p, x, k_cache, v_cache, lengths, layer, cfg: ModelConfig,
                         mesh_info, *, impl: str):
    """Choose the sharded split-KV path when enabled and applicable."""
    smax_chk = (k_cache[0] if cfg.kv_cache_dtype == "int8" else k_cache).shape[2]
    if (cfg.sharded_decode_attn and mesh_info is not None
            and mesh_info.n_model > 1
            and smax_chk % mesh_info.n_model == 0):
        return attn_decode_sharded(p, x, k_cache, v_cache, lengths, layer, cfg, mesh_info)
    return attn_decode(p, x, k_cache, v_cache, lengths, layer, cfg, impl=impl)
