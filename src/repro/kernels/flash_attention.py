"""Pallas TPU flash attention (prefill / training).

TPU-native design:
  * grid (B, H, nQ, nK); the K axis is innermost and sequential
    ("arbitrary" dimension semantics) so the running-softmax scratch
    carries across K blocks for a fixed Q block.
  * BlockSpec VMEM tiling: Q block (block_q, head_dim), K/V blocks
    (block_k, head_dim) — block sizes default to 128, matching the MXU
    systolic tile (128×128) and VPU lane width.
  * fp32 running max / sum / accumulator scratch in VMEM.
  * GQA: the kv-head index map folds the head-group (no HBM repeat).
  * causal / sliding-window blocks that are fully masked are skipped via
    pl.when (the index map still runs, but no FLOPs are issued).
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
    q_ref, k_ref, v_ref, o_ref,          # VMEM refs
    m_scr, l_scr, acc_scr,               # scratch
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, seq_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # visibility test for the whole block (skip fully-masked blocks)
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, q_start - (k_start + block_k - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                    # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool, window: Optional[int], scale: float,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
) -> jnp.ndarray:
    """q (B,S,H,D), k/v (B,S,KV,D) -> (B,S,H,D).  S padded to block size."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    grp = h // kv
    # BHSD layout inside the kernel
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = -(-s // block_q)
    nk = -(-s // block_k)
    pad_q = nq * block_q - s
    pad_k = nk * block_k - s
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    grid = (b, h, nq, nk)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, seq_len=s,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki, grp=grp: (bi, hi // grp, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki, grp=grp: (bi, hi // grp, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=compat.pltpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    out = out[:, :, :s]
    return jnp.moveaxis(out, 1, 2)
