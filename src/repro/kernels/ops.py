"""Public jit'd kernel wrappers with implementation dispatch.

impl selection:
  'auto'             -> pallas on TPU backend, memory-efficient jnp otherwise
  'pallas'           -> pl.pallas_call, TPU lowering
  'pallas_interpret' -> pl.pallas_call(interpret=True)  (CPU validation)
  'jnp'              -> chunked, memory-efficient pure-jnp (dry-run / CPU path)
  'ref'              -> the naive oracle from ref.py

The jnp implementations are written flash-style (lax.scan over KV / SSD
chunks with streaming softmax / state) so that the *dry-run* HLO has
realistic peak-memory behaviour — materializing (S, S) score matrices at
32k would make ``memory_analysis()`` meaningless.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

NEG_INF = -1e30


def _backend() -> str:
    return jax.default_backend()


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _backend() == "tpu" else "jnp"
    return impl


# ======================================================================
# Flash attention (prefill / training)
# ======================================================================

def _jnp_flash_attention(
    q, k, v, *, causal: bool, window: Optional[int], scale: float,
    block_k: int = 512,
):
    """Streaming-softmax attention: lax.scan over KV blocks. q (B,S,H,D)."""
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    grp = h // kv
    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, kv, grp, d)
    nblk = -(-sk // block_k)
    pad = nblk * block_k - sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = kp.reshape(b, nblk, block_k, kv, d)
    vb = vp.reshape(b, nblk, block_k, kv, d)
    qpos = jnp.arange(sq) + (sk - sq)  # right-aligned

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, blk_idx = inp  # (B,bk,KV,D) x2, scalar
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kblk.astype(jnp.float32))
        kpos = blk_idx * block_k + jnp.arange(block_k)
        mask = kpos[None, :] < sk
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((b, sq, kv, grp), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kv, grp), jnp.float32)
    a0 = jnp.zeros((b, sq, kv, grp, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, d).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "impl", "block_q", "block_k")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """Multi-head GQA attention. q (B,S,H,D), k/v (B,S,KV,D) -> (B,S,H,D)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.mha_reference(q, k, v, causal=causal, window=window, scale=scale)
    if impl == "jnp":
        return _jnp_flash_attention(q, k, v, causal=causal, window=window, scale=scale)
    from repro.kernels import flash_attention as _fa

    return _fa.flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=(impl == "pallas_interpret"),
    )


# ======================================================================
# Decode attention (single new token vs KV cache)
# ======================================================================

def _jnp_decode_attention(
    q, k_cache, v_cache, lengths, *, scale: float, window: Optional[int],
    block_k: int = 1024,
):
    """Streaming decode attention: scan over cache blocks. q (B,H,D)."""
    b, h, d = q.shape
    smax, kv = k_cache.shape[1], k_cache.shape[2]
    grp = h // kv
    qf = (q.astype(jnp.float32) * scale).reshape(b, kv, grp, d)
    nblk = -(-smax // block_k)
    pad = nblk * block_k - smax
    kp = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = kp.reshape(b, nblk, block_k, kv, d)
    vb = vp.reshape(b, nblk, block_k, kv, d)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, blk = inp
        s = jnp.einsum("bhgd,bkhd->bhgk", qf, kblk.astype(jnp.float32))
        kpos = blk * block_k + jnp.arange(block_k)
        mask = kpos[None, :] < lengths[:, None]
        if window is not None:
            mask &= kpos[None, :] >= (lengths[:, None] - window)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgk,bkhd->bhgd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((b, kv, grp), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, grp), jnp.float32)
    a0 = jnp.zeros((b, kv, grp, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, h, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "impl", "block_k"))
def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    impl: str = "auto",
    block_k: int = 512,
    layer: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Flash-decode. q (B,H,D), cache (B,Smax,KV,D), lengths (B,) -> (B,H,D).

    The caches may instead be the stacks (L,B,Smax,KV,D) of every layer,
    with ``layer`` (a scalar) the one the query attends to: the Pallas
    kernel reads it in place, the jnp paths slice it out."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    impl = _resolve(impl)
    if k_cache.ndim == 5 and impl not in ("pallas", "pallas_interpret"):
        k_cache, v_cache = k_cache[layer], v_cache[layer]
    if impl == "ref":
        return _ref.decode_attention_reference(
            q, k_cache, v_cache, lengths, scale=scale, window=window
        )
    if impl == "jnp":
        return _jnp_decode_attention(
            q, k_cache, v_cache, lengths, scale=scale, window=window
        )
    from repro.kernels import decode_attention as _da

    return _da.decode_attention_pallas(
        q, k_cache, v_cache, lengths, scale=scale, window=window,
        block_k=block_k, interpret=(impl == "pallas_interpret"), layer=layer,
    )


# ======================================================================
# Mamba2 SSD chunked scan
# ======================================================================

def _segsum_chunk(dA: jnp.ndarray) -> jnp.ndarray:
    """Inclusive within-chunk cumsum of dt*A.  dA (..., Q) -> (..., Q)."""
    return jnp.cumsum(dA, axis=-1)


def _jnp_ssd_chunked(x, dt, A, B, C, D, *, chunk: int, initial_state=None):
    """Chunked SSD (state-space dual) in pure jnp.  Shapes as ref.ssd_reference."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    reps = h // g
    nc = -(-s // chunk)
    pad = nc * chunk - s

    def pad_t(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    xf = pad_t(x.astype(jnp.float32)).reshape(b, nc, chunk, h, p)
    dtf = pad_t(dt.astype(jnp.float32)).reshape(b, nc, chunk, h)
    Bf = jnp.repeat(pad_t(B.astype(jnp.float32)), reps, axis=2).reshape(b, nc, chunk, h, n)
    Cf = jnp.repeat(pad_t(C.astype(jnp.float32)), reps, axis=2).reshape(b, nc, chunk, h, n)

    dA = dtf * A[None, None, None, :]              # (b,nc,Q,h)
    cs = jnp.cumsum(dA, axis=2)                    # inclusive cumsum within chunk
    # --- intra-chunk (quadratic, attention-like) ---
    # L[i,j] = exp(cs[i]-cs[j]) for i>=j else 0
    li = cs[:, :, :, None, :]                      # (b,nc,Q,1,h)
    lj = cs[:, :, None, :, :]                      # (b,nc,1,Q,h)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    L = jnp.where(causal, jnp.exp(li - lj), 0.0)   # (b,nc,Q,Q,h)
    scores = jnp.einsum("bcihn,bcjhn->bcijh", Cf, Bf) * L * dtf[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xf)
    # --- per-chunk end states ---
    decay_to_end = jnp.exp(cs[:, :, -1:, :] - cs)  # (b,nc,Q,h)
    S_c = jnp.einsum("bcjh,bcjhn,bcjhp->bchnp", decay_to_end * dtf, Bf, xf)
    dA_sum = cs[:, :, -1, :]                       # (b,nc,h)
    # --- inter-chunk state passing (sequential over nc) ---
    h0 = (
        initial_state.astype(jnp.float32).transpose(0, 1, 3, 2)  # (b,h,n,p)
        if initial_state is not None
        else jnp.zeros((b, h, n, p), jnp.float32)
    )

    def pass_state(hprev, inp):
        sc, da = inp                               # (b,h,n,p), (b,h)
        hnew = jnp.exp(da)[..., None, None] * hprev + sc
        return hnew, hprev                         # emit state *entering* the chunk

    h_final, h_in = jax.lax.scan(
        pass_state, h0, (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(dA_sum, 1, 0))
    )
    h_in = jnp.moveaxis(h_in, 0, 1)                # (b,nc,h,n,p)
    y_inter = jnp.einsum("bcihn,bchnp->bcihp", Cf * jnp.exp(cs)[..., None], h_in)
    y = (y_intra + y_inter).reshape(b, nc * chunk, h, p)[:, :s]
    y = y + D[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), h_final.transpose(0, 1, 3, 2)  # (b,h,p,n)


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    D: jnp.ndarray,
    *,
    chunk: int = 128,
    impl: str = "auto",
    initial_state: Optional[jnp.ndarray] = None,
):
    """Mamba2 SSD. Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.ssd_reference(x, dt, A, B, C, D, initial_state=initial_state)
    if impl == "jnp":
        return _jnp_ssd_chunked(x, dt, A, B, C, D, chunk=chunk, initial_state=initial_state)
    from repro.kernels import ssd_scan as _ssd

    return _ssd.ssd_scan_pallas(
        x, dt, A, B, C, D, chunk=chunk,
        initial_state=initial_state,
        interpret=(impl == "pallas_interpret"),
    )


def ssm_decode_step(x, dt, A, B, C, D, state):
    """One recurrent SSM step (decode).  x (B,H,P), dt (B,H), B/C (B,G,N),
    state (B,H,P,N) -> (y (B,H,P), new_state)."""
    b, h, p = x.shape
    g = B.shape[1]
    Bh = jnp.repeat(B, h // g, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C, h // g, axis=1).astype(jnp.float32)
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None, :])
    upd = dtf[..., None, None] * xf[..., :, None] * Bh[:, :, None, :]
    new_state = decay[..., None, None] * state + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch) + D[None, :, None] * xf
    return y.astype(x.dtype), new_state


def decode_attention_partials(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    lengths: jnp.ndarray, *, scale: Optional[float] = None,
    window: Optional[int] = None, impl: str = "auto", block_k: int = 512,
):
    """Split-KV flash-decode partials over a local cache slice:
    (acc (B,KV,G,D) f32 unnormalized, m (B,KV,G), l (B,KV,G)).

    `lengths` here is the EFFECTIVE length measured against THIS slice's
    global positions — masking against absolute positions is the caller's
    job (it passes position-offset-adjusted lengths or pre-masked caches).
    Used inside shard_map by models.attention.attn_decode_sharded; on TPU
    the Pallas kernel streams the slice through VMEM, on CPU the jnp path
    mirrors it exactly."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    impl = _resolve(impl)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import decode_attention as _da

        return _da.decode_attention_partials_pallas(
            q, k_cache, v_cache, lengths, scale=scale, window=window,
            block_k=block_k, interpret=(impl == "pallas_interpret"),
        )
    # jnp path (CPU / dry-run)
    b, h, d = q.shape
    smax, kv = k_cache.shape[1], k_cache.shape[2]
    grp = h // kv
    qf = (q.astype(jnp.float32) * scale).reshape(b, kv, grp, d)
    s_ = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32))
    kpos = jnp.arange(smax)[None, :]
    mask = kpos < lengths[:, None]
    if window is not None:
        mask &= kpos >= (lengths[:, None] - window)
    s_ = jnp.where(mask[:, None, None, :], s_, NEG_INF)
    m = s_.max(axis=-1)
    p_ = jnp.exp(s_ - m[..., None]) * mask[:, None, None, :]
    l = p_.sum(axis=-1)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p_, v_cache.astype(jnp.float32))
    return acc, m, l
