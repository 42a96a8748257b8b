"""Plain float32 reference of a llama-style decoder, and its fp8 control.

The forward pass follows the published llama description: token embedding;
per layer RMSNorm, causal grouped-query attention with rotary positions
(rotate-half form, ``theta`` from the config), residual, RMSNorm, SwiGLU MLP,
residual; final RMSNorm and the LM head.  No cache, no kernels, no batching
across sequences: each sequence runs whole, in float32, with every matrix
product at ``highest`` precision.  Weights are regenerated from the seed one
layer at a time (``weights.py``), so that the reference fits beside nothing.

``control=True`` is the control: the same pass with both operands of every
matrix product rounded to the precision below the configuration's
(``LOWER``: float8 e4m3 below bfloat16 or float16) with a per-row /
per-column absmax scale, the step a faster path might take.

This module imports nothing of the system under test.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

POS_PAD = 256         # scored positions are padded to a multiple of this
SEQ_PAD = 512         # sequences are padded to a multiple of this
# the precision below each served one, for the control
LOWER = {"bfloat16": jnp.float8_e4m3fn, "float16": jnp.float8_e4m3fn}


def _round(x, axis, dtype):
    """Round to ``dtype`` with an absmax scale along ``axis``, back to f32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / float(jnp.finfo(dtype).max)
    s = jnp.maximum(s, 1e-30)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _mm(a, b, lower=None):
    if lower is not None:
        a, b = _round(a, -1, lower), _round(b, 0, lower)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, hd): rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(h, w: Dict[str, jnp.ndarray], d: Dict[str, int], eps: float,
          theta: float, lower=None):
    """One decoder layer over one whole sequence. h (S, D) float32."""
    s = h.shape[0]
    H, KV, hd = d["H"], d["KV"], d["hd"]
    x = _rms(h, w["ln1"], eps)
    q = _rope(_mm(x, w["wq"], lower).reshape(s, H, hd), theta)
    k = _rope(_mm(x, w["wk"], lower).reshape(s, KV, hd), theta)
    v = _mm(x, w["wv"], lower).reshape(s, KV, hd)
    grp = H // KV
    k = jnp.repeat(k, grp, axis=1)
    v = jnp.repeat(v, grp, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)
    h = h + _mm(o.reshape(s, H * hd), w["wo"], lower)
    x = _rms(h, w["ln2"], eps)
    g = _mm(x, w["w_gate"], lower)
    u = _mm(x, w["w_up"], lower)
    return h + _mm(jax.nn.silu(g) * u, w["w_down"], lower)


class Reference:
    """Logits of the plain model at chosen positions of whole sequences.
    ``rows`` is the embedding's row count (the vocabulary as the served model
    pads it), so that the draw matches the served one; logits cover the
    config's vocabulary only.  Every sequence is padded to a multiple of
    ``SEQ_PAD`` (causal attention leaves earlier positions alone), so that a
    few programs serve every run of a configuration; none may pass
    ``max_len``."""

    def __init__(self, cfg: Dict, seed: int, rows: int, max_len: int,
                 control: bool = False):
        d = W.dims(cfg)
        self.n_layers, self.max_len = d["L"], max_len
        lower = None
        if control:
            if cfg["torch_dtype"] not in LOWER:
                raise ValueError(f"no control precision below {cfg['torch_dtype']!r}")
            lower = LOWER[cfg["torch_dtype"]]
        self.key = W.root_key(seed)
        eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])

        @jax.jit
        def _embed(key, toks):
            return W.embed_weights(key, cfg, rows)[0][toks].astype(jnp.float32)

        @jax.jit
        def _layer(key, li, h):
            w = {n: a.astype(jnp.float32) for n, a in
                 W.layer_weights(key, li, cfg).items()}
            return layer(h, w, d, eps, theta, lower)

        @jax.jit
        def _head(key, h, pos):
            _, head, norm = W.embed_weights(key, cfg, rows)
            x = _rms(h[pos], norm.astype(jnp.float32), eps)
            return _mm(x, head.astype(jnp.float32)[:, : d["V"]], lower)

        self._embed, self._layer, self._head = _embed, _layer, _head

    def logits(self, seqs: Sequence[Sequence[int]],
               positions: Sequence[Sequence[int]]) -> List[np.ndarray]:
        """For each sequence, float32 logits (len(positions[i]), vocab) at
        the given positions.  Layer by layer, one sequence at a time."""
        hs = []
        for s in seqs:
            if len(s) > self.max_len:
                raise ValueError(f"a sequence of {len(s)} tokens exceeds {self.max_len}")
            toks = np.zeros((-(-len(s) // SEQ_PAD) * SEQ_PAD,), np.int32)
            toks[: len(s)] = s
            hs.append(self._embed(self.key, jnp.asarray(toks)))
        for li in range(self.n_layers):
            hs = [self._layer(self.key, jnp.int32(li), h) for h in hs]
        out = []
        for h, pos in zip(hs, positions, strict=True):
            at = np.zeros((-(-len(pos) // POS_PAD) * POS_PAD,), np.int32)
            at[: len(pos)] = pos
            out.append(np.asarray(self._head(self.key, h, jnp.asarray(at)))[: len(pos)])
        return out
