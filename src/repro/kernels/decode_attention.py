"""Pallas TPU flash-decode: one query token vs a long KV cache.

Every decode step streams each layer's cache from HBM once.  Design:
  * the kernel reads the cache where it lies: a stack (L,B,Smax,KV,D) of
    every layer's cache, the layer picked by a scalar prefetch in the
    BlockSpec.  No layer's cache is sliced out or transposed for it; a
    single-layer cache (B,Smax,KV,D) is the stack of one.
  * grid (B, nS): per sequence the cache is streamed in (block_k, KV, D)
    VMEM tiles, all KV heads at once; the sequence axis is innermost and
    sequential so the running-softmax scratch carries across tiles.
  * each KV head's (block_k, D) rows are read out of the tile in VMEM, and
    its whole GQA head-group (grp = H/KV queries) rides along in one
    (grp, D) tile, amortizing each KV byte over grp queries — the kernel's
    arithmetic intensity is 2·grp FLOPs/byte.
  * fp32 scratch; out-of-length positions masked with the `lengths` scalar
    prefetch.
  * variable lengths + optional sliding window (rolling-buffer caches pass
    an effective length instead).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

NEG_INF = -1e30
# bytes of one (block_k, KV, D) cache tile: K and V, double-buffered, stay
# within the 16 MiB of VMEM a kernel may use by default, for any KV
TILE_BYTES = 1 << 20


def _kernel(
    lengths_ref, layer_ref,           # scalar prefetch (B,), (1,)
    q_ref, k_ref, v_ref, o_ref, *rest,
    scale: float, window: Optional[int], block_k: int, ragged: bool,
    partials: bool,
):
    if partials:
        m_out, l_out, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    kv, grp, _ = q_ref.shape
    bi = pl.program_id(0)
    si = pl.program_id(1)
    ns = pl.num_programs(1)
    length = lengths_ref[bi]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = si * block_k
    run = k_start < length
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 >= length - window)

    @pl.when(run)
    def _compute():
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (grp, block_k), 1)
        mask = kpos < length
        if window is not None:
            mask = jnp.logical_and(mask, kpos >= length - window)
        if ragged:
            # the last tile runs past the cache: zero its rows beyond the
            # length so that what lies there cannot reach the sum
            rows = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            live = rows < length
        for h in range(kv):
            q = q_ref[h].astype(jnp.float32) * scale               # (grp, d)
            k = k_ref[:, h, :].astype(jnp.float32)                 # (bk, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )                                                      # (grp, bk)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=1, keepdims=True)
            v = v_ref[:, h, :].astype(jnp.float32)                 # (bk, d)
            if ragged:
                v = jnp.where(live, v, 0.0)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[h] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        if partials:
            o_ref[...] = acc_scr[...].astype(o_ref.dtype)
            m_out[...] = m_scr[...]
            l_out[...] = l_scr[...]
        else:
            o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _call(q, k_cache, v_cache, lengths, layer, *, scale, window, block_k,
          interpret, partials):
    """Run the kernel over layer ``layer`` of a stacked cache
    (L,B,Smax,KV,D), or over one layer's cache (B,Smax,KV,D)."""
    if k_cache.ndim == 4:
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    b, h, d = q.shape
    smax, kv = k_cache.shape[2], k_cache.shape[3]
    grp = h // kv
    row_bytes = kv * d * k_cache.dtype.itemsize
    block_k = min(smax, block_k, max(8, TILE_BYTES // row_bytes))
    ns = -(-smax // block_k)
    qg = q.reshape(b, kv, grp, d)
    head_spec = pl.BlockSpec((None, kv, grp, d), lambda bi, si, *_: (bi, 0, 0, 0))
    cache_spec = pl.BlockSpec((None, None, block_k, kv, d),
                              lambda bi, si, lens, lay: (lay[0], bi, si, 0, 0))
    stat_spec = pl.BlockSpec((None, kv, grp, 1), lambda bi, si, *_: (bi, 0, 0, 0))
    if partials:
        out_specs = [head_spec, stat_spec, stat_spec]
        out_shape = [jax.ShapeDtypeStruct((b, kv, grp, d), jnp.float32),
                     jax.ShapeDtypeStruct((b, kv, grp, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, kv, grp, 1), jnp.float32)]
    else:
        out_specs = head_spec
        out_shape = jax.ShapeDtypeStruct((b, kv, grp, d), q.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, block_k=block_k,
                          ragged=smax % block_k != 0, partials=partials),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, ns),
            in_specs=[head_spec, cache_spec, cache_spec],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((kv, grp, 1), jnp.float32),
                pltpu.VMEM((kv, grp, 1), jnp.float32),
                pltpu.VMEM((kv, grp, d), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=compat.pltpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention_partials" if partials else "decode_attention",
    )(lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      qg, k_cache, v_cache)


def decode_attention_pallas(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    lengths: jnp.ndarray, *, scale: float, window: Optional[int],
    block_k: int = 512, interpret: bool = False, layer=None,
) -> jnp.ndarray:
    """q (B,H,D), cache (B,Smax,KV,D) — or the stack (L,B,Smax,KV,D) and a
    scalar ``layer`` — lengths (B,) -> (B,H,D)."""
    out = _call(q, k_cache, v_cache, lengths, layer, scale=scale, window=window,
                block_k=block_k, interpret=interpret, partials=False)
    return out.reshape(q.shape)


def decode_attention_partials_pallas(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    lengths: jnp.ndarray, *, scale: float, window: Optional[int],
    block_k: int = 512, interpret: bool = False,
):
    """Flash-decode PARTIALS for distributed split-KV combination: returns
    (acc (B,KV,G,D) fp32 unnormalized, m (B,KV,G) fp32, l (B,KV,G) fp32)
    over the LOCAL cache slice (B,Smax,KV,D).  The caller pmax/psum-combines
    across shards (models/attention.attn_decode_sharded)."""
    acc, m, lsum = _call(q, k_cache, v_cache, lengths, 0, scale=scale, window=window,
                         block_k=block_k, interpret=interpret, partials=True)
    return acc, m[..., 0], lsum[..., 0]
