"""Top-level model: init / train-forward / prefill / decode for all families.

Design notes
------------
* **scan-over-layers**: block params are stacked along a leading L axis and
  the forward is a single `jax.lax.scan`, so XLA compiles one block body
  regardless of depth (critical for the 80×-cell dry-run matrix).
* **hybrid (zamba2)**: the backbone is G groups of `attn_every` Mamba2 layers
  followed by ONE shared attention block (shared weights, fresh KV per
  application) plus a tail of `n_layers % attn_every` Mamba2 layers.
* **frontend stubs** (vlm/audio): prefill/train consume precomputed
  patch/frame embeddings (B,S,D) through a learned adapter; decode consumes
  token ids through the LM embedding table (text / EnCodec codes).
* caches are dicts of stacked per-layer arrays so decode is also a scan; the
  K/V stacks ride in its carry and each layer writes its new rows in place.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import embed_init, rms_norm, split_keys

MeshContext = moe_mod.MoEMeshInfo  # (mesh, batch_axes, model_axis, n_model, n_batch)


# ======================================================================
# Init
# ======================================================================

def _stack_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    D, Vp, L = cfg.d_model, cfg.padded_vocab, cfg.n_layers
    ks = split_keys(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], (Vp, D), dtype=dtype),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": embed_init(ks[1], (D, Vp), dtype=dtype),
    }
    if cfg.frontend != "tokens":
        params["frontend_proj"] = embed_init(ks[7], (D, D), dtype=dtype)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        def blk(k):
            k1, k2 = jax.random.split(k)
            b = {
                "ln1": jnp.ones((D,), dtype),
                "ln2": jnp.ones((D,), dtype),
                "attn": attn_mod.init_attn_params(k1, cfg, dtype),
            }
            if cfg.family == "moe":
                b["moe"] = moe_mod.init_moe_params(k2, cfg, dtype)
            else:
                b["mlp"] = mlp_mod.init_mlp_params(k2, cfg, dtype)
            return b

        params["blocks"] = _stack_init(blk, ks[2], L)
    elif cfg.family == "ssm":
        def blk(k):
            return {"ln": jnp.ones((D,), dtype), "ssm": ssm_mod.init_ssm_params(k, cfg, dtype)}

        params["blocks"] = _stack_init(blk, ks[2], L)
    elif cfg.family == "hybrid":
        def blk(k):
            return {"ln": jnp.ones((D,), dtype), "ssm": ssm_mod.init_ssm_params(k, cfg, dtype)}

        params["blocks"] = _stack_init(blk, ks[2], L)
        params["shared_attn"] = {
            "ln": jnp.ones((D,), dtype),
            "attn": attn_mod.init_attn_params(ks[3], cfg, dtype),
        }
    else:
        raise ValueError(cfg.family)
    return params


def hybrid_split(cfg: ModelConfig):
    """(n_groups, layers_per_group, n_tail)."""
    g = cfg.n_layers // cfg.attn_every
    return g, cfg.attn_every, cfg.n_layers - g * cfg.attn_every


def _tree_slice(tree, start, stop):
    return jax.tree.map(lambda a: a[start:stop], tree)


def _tree_reshape_groups(tree, g, k):
    return jax.tree.map(lambda a: a.reshape((g, k) + a.shape[1:]), tree)


# ======================================================================
# Embedding / head
# ======================================================================

def embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """batch has 'tokens' (B,S) int32 or 'embeds' (B,S,D)."""
    if "embeds" in batch:
        return batch["embeds"].astype(jnp.dtype(cfg.dtype)) @ params["frontend_proj"]
    return params["embed"][batch["tokens"]]


def lm_logits(params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    """Final norm + LM head; padded vocab tail masked to -inf.  fp32 out."""
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = (h @ params["lm_head"]).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(mask, logits, -1e30)
    return logits


# ======================================================================
# Block bodies (full-sequence)
# ======================================================================

def _attn_block(blk, h, cfg, impl, mesh_info):
    h = h + attn_mod.attn_forward(blk["attn"], rms_norm(h, blk["ln1"], cfg.norm_eps), cfg, impl=impl)
    if cfg.family == "moe":
        y, aux = moe_mod.moe_forward(blk["moe"], rms_norm(h, blk["ln2"], cfg.norm_eps), cfg, mesh_info)
        return h + y, aux["lb_loss"]
    h = h + mlp_mod.mlp_forward(blk["mlp"], rms_norm(h, blk["ln2"], cfg.norm_eps))
    return h, jnp.float32(0.0)


def _ssm_block(blk, h, cfg, impl):
    return h + ssm_mod.ssm_forward(blk["ssm"], rms_norm(h, blk["ln"], cfg.norm_eps), cfg, impl=impl)


def _resolve_impl(cfg: ModelConfig) -> str:
    return cfg.attn_impl if cfg.attn_impl != "auto" else "auto"


# ======================================================================
# Train / full-sequence forward
# ======================================================================

def _act_constraint(h, cfg, mesh_info):
    """FSDP mode: pin activations batch-sharded over every mesh axis so the
    partitioner gathers weights instead of re-sharding activations."""
    if not (cfg.fsdp_act_constraint and mesh_info is not None):
        return h
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.shardings import fsdp_batch_axes
    axes = fsdp_batch_axes(mesh_info.mesh, h.shape[0])
    return jax.lax.with_sharding_constraint(
        h, NamedSharding(mesh_info.mesh, P(axes, None, None)))


def forward(
    params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
    mesh_info: Optional[MeshContext] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.  Returns (logits (B,S,Vp) fp32, aux_loss scalar)."""
    impl = _resolve_impl(cfg)
    h = embed_inputs(params, cfg, batch)
    h = _act_constraint(h, cfg, mesh_info)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        def body(carry, blk):
            h, lb = carry
            h = _act_constraint(h, cfg, mesh_info)
            h, lb_i = _attn_block(blk, h, cfg, impl, mesh_info)
            h = _act_constraint(h, cfg, mesh_info)
            return (h, lb + lb_i), None

        if cfg.remat:
            body = jax.checkpoint(body)
        (h, lb), _ = jax.lax.scan(body, (h, jnp.float32(0.0)), params["blocks"])
        return lm_logits(params, cfg, h), lb / cfg.n_layers

    if cfg.family == "ssm":
        def body(h, blk):
            h = _act_constraint(h, cfg, mesh_info)
            return _ssm_block(blk, h, cfg, impl), None

        if cfg.remat:
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, params["blocks"])
        return lm_logits(params, cfg, h), jnp.float32(0.0)

    # hybrid: groups of (attn_every mamba layers + shared attention) + tail
    g, kpg, tail = hybrid_split(cfg)
    shared = params["shared_attn"]

    def inner(h, blk):
        h = _act_constraint(h, cfg, mesh_info)
        return _ssm_block(blk, h, cfg, impl), None

    if cfg.remat:
        inner = jax.checkpoint(inner)

    main = _tree_reshape_groups(_tree_slice(params["blocks"], 0, g * kpg), g, kpg)

    def outer(h, grp_blocks):
        h, _ = jax.lax.scan(inner, h, grp_blocks)
        h = h + attn_mod.attn_forward(
            shared["attn"], rms_norm(h, shared["ln"], cfg.norm_eps), cfg, impl=impl
        )
        return h, None

    h, _ = jax.lax.scan(outer, h, main)
    if tail:
        tail_blocks = _tree_slice(params["blocks"], g * kpg, cfg.n_layers)
        h, _ = jax.lax.scan(inner, h, tail_blocks)
    return lm_logits(params, cfg, h), jnp.float32(0.0)


# ======================================================================
# Cache init / prefill / decode
# ======================================================================

def _kv_smax(cfg: ModelConfig, max_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(max_len, cfg.sliding_window)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    KV, hd = cfg.eff_n_kv_heads, cfg.resolved_head_dim
    cache: Dict[str, Any] = {"lengths": jnp.zeros((batch,), jnp.int32)}
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        smax = _kv_smax(cfg, max_len)
        if cfg.kv_cache_dtype == "int8":
            cache["k"] = (jnp.zeros((cfg.n_layers, batch, smax, KV, hd), jnp.int8),
                          jnp.zeros((cfg.n_layers, batch, smax, KV), jnp.float32))
            cache["v"] = (jnp.zeros((cfg.n_layers, batch, smax, KV, hd), jnp.int8),
                          jnp.zeros((cfg.n_layers, batch, smax, KV), jnp.float32))
        else:
            cache["k"] = jnp.zeros((cfg.n_layers, batch, smax, KV, hd), dtype)
            cache["v"] = jnp.zeros((cfg.n_layers, batch, smax, KV, hd), dtype)
    elif cfg.family == "ssm":
        st = ssm_mod.init_ssm_state(cfg, batch)
        cache["ssm_state"] = tuple(
            jnp.broadcast_to(a, (cfg.n_layers,) + a.shape) for a in st
        )
    elif cfg.family == "hybrid":
        g, kpg, tail = hybrid_split(cfg)
        st = ssm_mod.init_ssm_state(cfg, batch)
        cache["ssm_state"] = tuple(
            jnp.broadcast_to(a, (cfg.n_layers,) + a.shape) for a in st
        )
        cache["k"] = jnp.zeros((g, batch, max_len, KV, hd), dtype)
        cache["v"] = jnp.zeros((g, batch, max_len, KV, hd), dtype)
    return cache


def prefill(
    params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], max_len: int,
    mesh_info: Optional[MeshContext] = None,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Run the prompt; returns (last-position logits (B,Vp), filled cache)."""
    impl = _resolve_impl(cfg)
    h = embed_inputs(params, cfg, batch)
    b, s, _ = h.shape
    cache: Dict[str, Any] = {"lengths": jnp.full((b,), s, jnp.int32)}

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        smax = _kv_smax(cfg, max_len)

        def body(carry, blk):
            h, lb = carry
            with jax.named_scope("attention"):
                x = rms_norm(h, blk["ln1"], cfg.norm_eps)
                o, kc, vc = attn_mod.attn_prefill(blk["attn"], x, cfg, smax, impl=impl)
                h = h + o
            with jax.named_scope("mlp"):
                if cfg.family == "moe":
                    y, aux = moe_mod.moe_forward(blk["moe"], rms_norm(h, blk["ln2"], cfg.norm_eps), cfg, mesh_info)
                    h = h + y
                    lb = lb + aux["lb_loss"]
                else:
                    h = h + mlp_mod.mlp_forward(blk["mlp"], rms_norm(h, blk["ln2"], cfg.norm_eps))
            return (h, lb), (kc, vc)

        if cfg.remat:
            body = jax.checkpoint(body)
        (h, _), (kc, vc) = jax.lax.scan(body, (h, jnp.float32(0.0)), params["blocks"])
        cache["k"], cache["v"] = kc, vc
        return lm_logits(params, cfg, h[:, -1]), cache

    if cfg.family == "ssm":
        def body(h, blk):
            x = rms_norm(h, blk["ln"], cfg.norm_eps)
            o, st = ssm_mod.ssm_forward(blk["ssm"], x, cfg, impl=impl, return_state=True)
            return h + o, st

        if cfg.remat:
            body = jax.checkpoint(body)
        h, st = jax.lax.scan(body, h, params["blocks"])
        cache["ssm_state"] = st
        return lm_logits(params, cfg, h[:, -1]), cache

    # hybrid
    g, kpg, tail = hybrid_split(cfg)
    shared = params["shared_attn"]

    def inner(h, blk):
        x = rms_norm(h, blk["ln"], cfg.norm_eps)
        o, st = ssm_mod.ssm_forward(blk["ssm"], x, cfg, impl=impl, return_state=True)
        return h + o, st

    if cfg.remat:
        inner = jax.checkpoint(inner)
    main = _tree_reshape_groups(_tree_slice(params["blocks"], 0, g * kpg), g, kpg)

    def outer(h, grp_blocks):
        h, states = jax.lax.scan(inner, h, grp_blocks)
        x = rms_norm(h, shared["ln"], cfg.norm_eps)
        o, kc, vc = attn_mod.attn_prefill(shared["attn"], x, cfg, max_len, impl=impl)
        return h + o, (states, kc, vc)

    h, (main_states, kc, vc) = jax.lax.scan(outer, h, main)
    main_states = tuple(a.reshape((g * kpg,) + a.shape[2:]) for a in main_states)
    if tail:
        h, tail_states = jax.lax.scan(inner, h, _tree_slice(params["blocks"], g * kpg, cfg.n_layers))
        main_states = tuple(
            jnp.concatenate([m, t], axis=0) for m, t in zip(main_states, tail_states, strict=True)
        )
    cache["ssm_state"] = main_states
    cache["k"], cache["v"] = kc, vc
    return lm_logits(params, cfg, h[:, -1]), cache


def decode_step(
    params, cfg: ModelConfig, cache: Dict[str, Any], tokens: jnp.ndarray,
    mesh_info: Optional[MeshContext] = None,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step.  tokens (B,) int32 -> (logits (B,Vp) fp32, new cache)."""
    impl = _resolve_impl(cfg)
    lengths = cache["lengths"]
    h = params["embed"][tokens][:, None, :]            # (B,1,D)
    new_cache: Dict[str, Any] = {"lengths": lengths + 1}

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        def body(carry, xs):
            h, kc, vc = carry
            blk, layer = xs
            with jax.named_scope("attention"):
                x = rms_norm(h, blk["ln1"], cfg.norm_eps)
                o, kc, vc = attn_mod.attn_decode_dispatch(
                    blk["attn"], x, kc, vc, lengths, layer, cfg, mesh_info, impl=impl)
                h = h + o
            with jax.named_scope("mlp"):
                if cfg.family == "moe":
                    y, _ = moe_mod.moe_forward(blk["moe"], rms_norm(h, blk["ln2"], cfg.norm_eps), cfg, mesh_info)
                    h = h + y
                else:
                    h = h + mlp_mod.mlp_forward(blk["mlp"], rms_norm(h, blk["ln2"], cfg.norm_eps))
            return (h, kc, vc), None

        # the stacked cache rides in the loop carry: each layer writes its
        # new rows in place, and no layer's cache is copied in or out
        with jax.named_scope("layers"):
            (h, kc, vc), _ = jax.lax.scan(
                body, (h, cache["k"], cache["v"]),
                (params["blocks"], jnp.arange(cfg.n_layers)))
        new_cache["k"], new_cache["v"] = kc, vc
        return lm_logits(params, cfg, h[:, 0]), new_cache

    if cfg.family == "ssm":
        def body(h, xs):
            blk, st = xs
            x = rms_norm(h, blk["ln"], cfg.norm_eps)
            o, st = ssm_mod.ssm_decode(blk["ssm"], x, st, cfg)
            return h + o, st

        h, st = jax.lax.scan(body, h, (params["blocks"], cache["ssm_state"]))
        new_cache["ssm_state"] = st
        return lm_logits(params, cfg, h[:, 0]), new_cache

    # hybrid
    g, kpg, tail = hybrid_split(cfg)
    shared = params["shared_attn"]

    def inner(h, xs):
        blk, st = xs
        x = rms_norm(h, blk["ln"], cfg.norm_eps)
        o, st = ssm_mod.ssm_decode(blk["ssm"], x, st, cfg)
        return h + o, st

    main_blocks = _tree_reshape_groups(_tree_slice(params["blocks"], 0, g * kpg), g, kpg)
    main_st = tuple(
        a[: g * kpg].reshape((g, kpg) + a.shape[1:]) for a in cache["ssm_state"]
    )

    def outer(carry, xs):
        h, kc, vc = carry
        grp_blocks, st_g, gi = xs
        h, st_g = jax.lax.scan(inner, h, (grp_blocks, st_g))
        x = rms_norm(h, shared["ln"], cfg.norm_eps)
        o, kc, vc = attn_mod.attn_decode_dispatch(
            shared["attn"], x, kc, vc, lengths, gi, cfg, mesh_info, impl=impl)
        return (h + o, kc, vc), st_g

    (h, kc, vc), st_g = jax.lax.scan(
        outer, (h, cache["k"], cache["v"]), (main_blocks, main_st, jnp.arange(g))
    )
    new_st = tuple(a.reshape((g * kpg,) + a.shape[2:]) for a in st_g)
    if tail:
        h, st_t = jax.lax.scan(
            inner, h,
            (_tree_slice(params["blocks"], g * kpg, cfg.n_layers),
             tuple(a[g * kpg :] for a in cache["ssm_state"])),
        )
        new_st = tuple(jnp.concatenate([m, t], axis=0) for m, t in zip(new_st, st_t, strict=True))
    new_cache["ssm_state"] = new_st
    new_cache["k"], new_cache["v"] = kc, vc
    return lm_logits(params, cfg, h[:, 0]), new_cache
