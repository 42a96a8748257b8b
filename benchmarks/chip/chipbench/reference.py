"""What every architecture's plain float32 reference shares, and its control.

An architecture's reference, ``references/<reference>.py``, found by the
configuration's ``reference`` key, gives ``dims(cfg)``, one layer's seeded
weights (``layer_weights``) and one layer's forward pass over one whole
sequence (``layer``); ``Layerwise`` runs it: token embedding, the layers one
at a time, final RMSNorm and the LM head.  No cache, no kernels, no batching
across sequences: each sequence runs whole, in float32, with every matrix
product at ``highest`` precision.  Weights are regenerated from the seed one
layer at a time (``weights.py``), so that the reference fits beside nothing.
``attention`` is the causal grouped-query block that decoders share.

``control=True`` is the control: the same pass with both operands of every
matrix product rounded to the precision below the configuration's
(``LOWER``: float8 e4m3 below bfloat16 or float16, bfloat16 below float32)
with a per-row / per-column absmax scale, the step a faster path might take.

This module imports nothing of the system under test.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

POS_PAD = 256         # scored positions are padded to a multiple of this
SEQ_PAD = 512         # sequences are padded to a multiple of this
# the precision below each served one, for the control
LOWER = {"bfloat16": jnp.float8_e4m3fn, "float16": jnp.float8_e4m3fn,
         "float32": jnp.bfloat16}


def round_to(x, axis, dtype):
    """Round to ``dtype`` with an absmax scale along ``axis``, back to f32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / float(jnp.finfo(dtype).max)
    s = jnp.maximum(s, 1e-30)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def mm(a, b, lower=None):
    """``a @ b`` at ``highest``; with ``lower`` both operands are first
    rounded to that precision (the control)."""
    if lower is not None:
        a, b = round_to(a, -1, lower), round_to(b, 0, lower)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def attention_shapes(d: Dict[str, int]) -> Dict[str, Tuple[int, ...]]:
    """The leaves ``attention`` reads, in the order that fixes their keys."""
    D, H, KV, hd = d["D"], d["H"], d["KV"], d["hd"]
    return {"ln1": (D,), "ln2": (D,),
            "wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd), "wo": (H * hd, D)}


def rope(x, theta):
    """x (S, H, hd): rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, w: Dict[str, jnp.ndarray], d: Dict[str, int], eps: float,
              theta: Optional[float], lower=None):
    """RMSNorm (``ln1``), causal grouped-query attention, residual; rotary
    positions where ``theta`` is given.  h (S, D) float32."""
    s = h.shape[0]
    H, KV, hd = d["H"], d["KV"], d["hd"]
    x = rms(h, w["ln1"], eps)
    q = mm(x, w["wq"], lower).reshape(s, H, hd)
    k = mm(x, w["wk"], lower).reshape(s, KV, hd)
    v = mm(x, w["wv"], lower).reshape(s, KV, hd)
    if theta is not None:
        q, k = rope(q, theta), rope(k, theta)
    grp = H // KV
    k = jnp.repeat(k, grp, axis=1)
    v = jnp.repeat(v, grp, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)
    return h + mm(o.reshape(s, H * hd), w["wo"], lower)


class Layerwise:
    """Logits of the plain model at chosen positions of whole sequences.

    ``ref`` is the configuration's reference module: ``ref.layer(h, w, cfg,
    lower)`` maps h (S, D) float32 to the next h, with layer ``li``'s
    weights ``ref.layer_weights(key, li, cfg, dtype)`` drawn in the served
    dtype (``torch_dtype``) and cast to float32, its matrix products through
    ``mm(..., lower)``.  ``rows`` is the embedding's row count (the
    vocabulary as the served model pads it), so that the draw matches the
    served one; logits cover the config's vocabulary only.  Every sequence
    is padded to a multiple of ``SEQ_PAD`` (causal layers leave earlier
    positions alone), so that a few programs serve every run of a
    configuration; none may pass ``max_len``."""

    def __init__(self, ref, cfg: Dict, seed: int, rows: int, max_len: int,
                 control: bool = False):
        self.n_layers, self.max_len = int(ref.dims(cfg)["L"]), max_len
        lower = None
        if control:
            if cfg["torch_dtype"] not in LOWER:
                raise ValueError(f"no control precision below {cfg['torch_dtype']!r}")
            lower = LOWER[cfg["torch_dtype"]]
        self.key = W.root_key(seed)
        eps, vocab = float(cfg["rms_norm_eps"]), int(cfg["vocab_size"])
        dtype = jnp.dtype(cfg["torch_dtype"])

        @jax.jit
        def _embed(key, toks):
            return W.embed_weights(key, cfg, rows, dtype)[0][toks].astype(jnp.float32)

        @jax.jit
        def _layer(key, li, h):
            w = {n: a.astype(jnp.float32)
                 for n, a in ref.layer_weights(key, li, cfg, dtype).items()}
            return ref.layer(h, w, cfg, lower)

        @jax.jit
        def _head(key, h, pos):
            _, head, norm = W.embed_weights(key, cfg, rows, dtype)
            x = rms(h[pos], norm.astype(jnp.float32), eps)
            return mm(x, head.astype(jnp.float32)[:, :vocab], lower)

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
