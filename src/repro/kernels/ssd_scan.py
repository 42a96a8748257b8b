"""Pallas TPU Mamba2 SSD chunked scan.

Single-kernel design exploiting TPU sequential grid semantics: grid
(B, H, nChunks) with the chunk axis innermost and "arbitrary" (sequential),
so a VMEM scratch carries the recurrent inter-chunk state (N, P) across
chunks of the same (batch, head) — the TPU-native replacement for the
multi-kernel Triton decomposition (chunk_state / state_passing /
chunk_scan) used on GPU.

Per (b, h, c) the kernel computes, entirely in VMEM:
  * inclusive decay cumsum  cs = cumsum(dt·A)               (Q,)
  * inter-chunk:  Y_inter = exp(cs)·(C @ S_prev)            (Q,P)
  * intra-chunk:  scores  = (C @ Bᵀ) ⊙ L ⊙ dtⱼ, L = exp(csᵢ−csⱼ)·causal
                  Y_intra = scores @ X                      (Q,P)
  * state update: S = exp(cs[Q−1])·S_prev + (decay_to_end·dt·B)ᵀ @ X
Chunk Q defaults to 128 (MXU-aligned); head_dim P and state N are 64/128.
The per-head scalars A and D ride whole in SMEM, indexed by the head id: a
(1, 1) VMEM block of an (H, 1) array breaks the TPU tiling rule (a block's
last two dims must be divisible by (8, 128) or equal the array's).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat


def _kernel(
    x_ref, dt_ref, dt_row_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
    y_ref, hout_ref,
    state_scr,
    *, chunk: int, use_h0: bool,
):
    hi = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        if use_h0:
            state_scr[...] = h0_ref[0, 0].astype(jnp.float32)   # (N, P)
        else:
            state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)      # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)    # (Q, 1)
    dt_row = dt_row_ref[0, 0, 0].astype(jnp.float32)  # (1, Q)
    a = a_ref[hi]                               # scalar (SMEM)
    bmat = b_ref[0, 0, 0].astype(jnp.float32)   # (Q, N)
    cmat = c_ref[0, 0, 0].astype(jnp.float32)   # (Q, N)
    dcoef = d_ref[hi]                           # scalar (SMEM)

    # Mosaic has no cumsum: the inclusive prefix sum of dt*A is a matmul
    # with a triangular ones matrix, once per layout (column and row), all
    # kept 2-D.  HIGHEST keeps the sum in f32 on the MXU.
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_i >= iota_j
    tri = causal.astype(jnp.float32)
    hp = jax.lax.Precision.HIGHEST
    da = dt * a                                 # (Q, 1)
    cs = jnp.dot(tri, da, precision=hp)         # (Q, 1) inclusive cumsum
    cs_row = jnp.dot(dt_row * a, tri.T, precision=hp)   # (1, Q)
    total = jnp.sum(da, axis=0, keepdims=True)  # (1, 1) = cs[Q-1]

    s_prev = state_scr[...]                     # (N, P)
    # inter-chunk contribution
    y_inter = jnp.exp(cs) * jax.lax.dot_general(
        cmat, s_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                           # (Q, P)
    # intra-chunk quadratic part: L[i,j] = exp(cs[i]-cs[j]) for i>=j else 0
    lmat = jnp.where(causal, jnp.exp(cs - cs_row), 0.0)
    cb = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                           # (Q, Q)
    scores = cb * lmat * dt_row
    y = y_inter + jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y = y + dcoef * x
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update: S_new = exp(cs[-1]) * S_prev + sum_j w_j * B_j (outer) X_j
    w = jnp.exp(total - cs) * dt                # (Q, 1)
    s_new = jnp.exp(total) * s_prev + jax.lax.dot_general(
        bmat * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                           # (N, P)
    state_scr[...] = s_new

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = s_new


def ssd_scan_pallas(
    x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
    C: jnp.ndarray, D: jnp.ndarray, *, chunk: int = 128,
    initial_state: Optional[jnp.ndarray] = None, interpret: bool = False,
):
    """Shapes as ops.ssd_scan: x (B,S,H,P), dt (B,S,H), A (H,), B/C (B,S,G,N),
    D (H,) -> (y (B,S,H,P), final_state (B,H,P,N))."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    reps = h // g
    nc = -(-s // chunk)
    pad = nc * chunk - s

    def pad_seq(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    # layouts: per-(b,h) tiles
    xt = jnp.moveaxis(pad_seq(x), 2, 1).reshape(b, h, nc, chunk, p)
    dtt = jnp.moveaxis(pad_seq(dt), 2, 1).reshape(b, h, nc, chunk, 1)
    dtt_row = dtt.reshape(b, h, nc, 1, chunk)
    bt = jnp.repeat(jnp.moveaxis(pad_seq(B), 2, 1), reps, axis=1).reshape(b, h, nc, chunk, n)
    ct = jnp.repeat(jnp.moveaxis(pad_seq(C), 2, 1), reps, axis=1).reshape(b, h, nc, chunk, n)
    a2 = A.astype(jnp.float32)
    d2 = D.astype(jnp.float32)
    use_h0 = initial_state is not None
    h0 = (
        initial_state.transpose(0, 1, 3, 2).astype(jnp.float32)  # (B,H,N,P)
        if use_h0
        else jnp.zeros((b, h, n, p), jnp.float32)
    )

    grid = (b, h, nc)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, hout = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, use_h0=use_h0),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            smem,
            pl.BlockSpec((1, 1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            smem,
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=compat.pltpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xt, dtt, dtt_row, a2, bt, ct, d2, h0)
    y = y.reshape(b, h, nc * chunk, p)[:, :, :s]
    y = jnp.moveaxis(y, 1, 2)                    # (B,S,H,P)
    return y.astype(x.dtype), hout.transpose(0, 1, 3, 2)  # (B,H,P,N)
