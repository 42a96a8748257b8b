"""Pallas TPU flash-decode: one query token vs a long KV cache.

Decode attention is memory-bound (every step streams the whole cache from
HBM).  Design:
  * grid (B, KV, nS): per (batch, kv-head) the cache is streamed in
    (block_k, head_dim) VMEM tiles; the sequence axis is innermost and
    sequential so the running-softmax scratch carries across tiles.
  * the whole GQA head-group (grp = H/KV queries) rides along in one
    (grp, head_dim) VMEM tile, amortizing each KV byte over grp queries —
    the kernel's arithmetic intensity is 2·grp FLOPs/byte.
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


def _kernel(
    lengths_ref,                      # scalar prefetch (B,)
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, window: Optional[int], block_k: int,
    partials: bool = False, m_out=None, l_out=None,
):
    bi = pl.program_id(0)
    si = pl.program_id(2)
    ns = pl.num_programs(2)
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
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (grp, d)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                      # (grp, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window is not None:
            mask = jnp.logical_and(mask, kpos >= length - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                    # (bk, d)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        if partials:
            o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        else:
            o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _kernel_partials(
    lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, window: Optional[int], block_k: int,
):
    _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            scale=scale, window=window, block_k=block_k, partials=True)
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == ns - 1)
    def _emit_stats():
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def decode_attention_pallas(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    lengths: jnp.ndarray, *, scale: float, window: Optional[int],
    block_k: int = 512, interpret: bool = False,
) -> jnp.ndarray:
    """q (B,H,D), cache (B,Smax,KV,D), lengths (B,) -> (B,H,D)."""
    b, h, d = q.shape
    smax, kv = k_cache.shape[1], k_cache.shape[2]
    grp = h // kv
    block_k = min(block_k, smax)
    ns = -(-smax // block_k)
    pad = ns * block_k - smax
    kt = jnp.moveaxis(k_cache, 2, 1)          # (B,KV,S,D)
    vt = jnp.moveaxis(v_cache, 2, 1)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(b, kv, grp, d)

    grid = (b, kv, ns)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, grp, d), lambda bi, hi, si, *_: (bi, hi, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, si, *_: (bi, hi, si, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, si, *_: (bi, hi, si, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, grp, d), lambda bi, hi, si, *_: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((grp, 1), jnp.float32),
                pltpu.VMEM((grp, 1), jnp.float32),
                pltpu.VMEM((grp, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, grp, d), q.dtype),
        compiler_params=compat.pltpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(b, h, d)


def decode_attention_partials_pallas(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    lengths: jnp.ndarray, *, scale: float, window: Optional[int],
    block_k: int = 512, interpret: bool = False,
):
    """Flash-decode PARTIALS for distributed split-KV combination: returns
    (acc (B,KV,G,D) fp32 unnormalized, m (B,KV,G) fp32, l (B,KV,G) fp32)
    over the LOCAL cache slice.  The caller pmax/psum-combines across
    shards (models/attention.attn_decode_sharded)."""
    b, h, d = q.shape
    smax, kv = k_cache.shape[1], k_cache.shape[2]
    grp = h // kv
    block_k = min(block_k, smax)
    ns = -(-smax // block_k)
    pad = ns * block_k - smax
    kt = jnp.moveaxis(k_cache, 2, 1)
    vt = jnp.moveaxis(v_cache, 2, 1)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(b, kv, grp, d)
    grid = (b, kv, ns)
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel_partials, scale=scale, window=window,
                          block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, grp, d), lambda bi, hi, si, *_: (bi, hi, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, si, *_: (bi, hi, si, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, si, *_: (bi, hi, si, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, grp, d), lambda bi, hi, si, *_: (bi, hi, 0, 0)),
                pl.BlockSpec((1, 1, grp, 1), lambda bi, hi, si, *_: (bi, hi, 0, 0)),
                pl.BlockSpec((1, 1, grp, 1), lambda bi, hi, si, *_: (bi, hi, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((grp, 1), jnp.float32),
                pltpu.VMEM((grp, 1), jnp.float32),
                pltpu.VMEM((grp, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, grp, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, grp, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, grp, 1), jnp.float32),
        ],
        compiler_params=compat.pltpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention_partials",
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return acc, m[..., 0], l[..., 0]
