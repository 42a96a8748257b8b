"""What every architecture's plain float32 reference shares, and its control.

An architecture's reference, ``references/<reference>.py``, found by the
configuration's ``reference`` key, gives ``dims(cfg)``, one layer's seeded
weights (``layer_weights``) and one layer's forward pass over one whole
sequence (``layer``); ``Layerwise`` runs it: token embedding, the layers one
at a time, final RMSNorm and the LM head.  Layers may differ in kind: the
reference's ``layer_kinds(cfg)`` lists the kind of each layer it runs (one
kind, ``weights.ONE_KIND``, where it gives none), and ``layer_shapes``,
``layer_weights`` and ``layer`` take the kind.  A reference may scale the
embedding's output and the head's logits (``embedding_multiplier(cfg)``,
``logits_multiplier(cfg)``, each 1 where it gives none).  No cache, no
kernels, no batching across sequences: each sequence runs whole, in
float32, with every product at ``highest`` precision.  Weights are
regenerated from the seed one layer at a time (``weights.py``), so that the
reference fits beside nothing.

Shared blocks: ``attention``, the causal grouped-query block that decoders
share (rotary or, with ``theta=None``, no positions; its score scale and
residual weight may be set), and ``mamba2``, the Mamba-2 (SSD) mixer, with
``mamba2_rules``, the draws of its leaves that are neither a norm nor a
projection.

``control=True`` is the control: the same pass with both operands of every
matrix product rounded to the precision below the configuration's
(``LOWER``: float8 e4m3 below bfloat16 or float16, bfloat16 below float32)
with a per-row / per-column absmax scale, the step a faster path might take.

This module imports nothing of the system under test.
"""
from __future__ import annotations

import functools
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
              theta: Optional[float], lower=None, scale: Optional[float] = None,
              residual: float = 1.0):
    """RMSNorm (``ln1``), causal grouped-query attention, residual; rotary
    positions where ``theta`` is given, none with ``theta=None``.  Scores are
    scaled by ``scale`` (``hd ** -0.5`` where None) and the block's output is
    added at weight ``residual``.  h (S, D) float32."""
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
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=jax.lax.Precision.HIGHEST) * (
        hd ** -0.5 if scale is None else scale)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)
    return h + residual * mm(o.reshape(s, H * hd), w["wo"], lower)


# Mamba-2's published draws (``mamba_ssm``'s ``Mamba2``) of the leaves that are
# neither a norm scale nor a projection: A = U(1, 16), held as its log; dt
# log-uniform in [DT_MIN, DT_MAX], held as its inverse softplus (the published
# clamp of dt at 1e-4 lies below DT_MIN and never binds); D = 1; the conv bias
# as PyTorch's ``Conv1d`` draws it, U(-K**-0.5, K**-0.5) for a depthwise
# kernel of width K, which ``mamba_ssm`` leaves in place (Hugging Face's port
# zeroes it; a zero bias would leave the served bias path unchecked).
DT_MIN, DT_MAX = 1e-3, 1e-1


def _dt_bias(key, shape):
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN))
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_bias(key, shape, width):
    return jax.random.uniform(key, shape, jnp.float32, -width ** -0.5, width ** -0.5)


def mamba2_rules(d: Dict[str, int]):
    """The draw rules (``weights.layer_draw``) of ``mamba2``'s leaves that are
    neither a norm scale nor a projection."""
    conv = functools.partial(_conv_bias, width=d["conv"])
    return {"dt_bias": _dt_bias,
            "A_log": lambda key, shape: jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                                                   1.0, 16.0)),
            "D": lambda key, shape: jnp.ones(shape, jnp.float32),
            "conv_x_b": conv, "conv_B_b": conv, "conv_C_b": conv}


def mamba2_shapes(d: Dict[str, int]) -> Dict[str, Tuple[int, ...]]:
    """The leaves ``mamba2`` reads, in the order that fixes their keys.  ``d``
    gives ``D`` and the mixer's ``ssm_heads``, ``ssm_head_dim``,
    ``ssm_state``, ``ssm_groups`` and ``conv`` (the kernel's width)."""
    D, nh, K = d["D"], d["ssm_heads"], d["conv"]
    di, gn = nh * d["ssm_head_dim"], d["ssm_groups"] * d["ssm_state"]
    return {"ln": (D,), "w_z": (D, di), "w_x": (D, di), "w_B": (D, gn), "w_C": (D, gn),
            "w_dt": (D, nh), "conv_x_w": (K, di), "conv_x_b": (di,),
            "conv_B_w": (K, gn), "conv_B_b": (gn,), "conv_C_w": (K, gn), "conv_C_b": (gn,),
            "dt_bias": (nh,), "A_log": (nh,), "D": (nh,), "ln_gate": (di,),
            "out_proj": (di, D)}


def _causal_conv(x, k, b):
    """Depthwise causal convolution: y[t] = b + sum_i k[i] * x[t - K + 1 + i],
    zeros before the first position.  x (S, C), k (K, C), b (C,)."""
    width, s = k.shape[0], x.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(xp[i:i + s] * k[i] for i in range(width)) + b


def mamba2(h, w: Dict[str, jnp.ndarray], d: Dict[str, int], eps: float, lower=None,
           residual: float = 1.0):
    """The Mamba-2 (SSD) mixer (arXiv:2405.21060), with its RMSNorm and
    residual.  h (S, D) float32.

    u = RMSNorm(h, ``ln``); z, x, B, C and dt are u's projections (the column
    blocks of the published fused ``in_proj``: z | x | B | C | dt); x, B and C
    each pass a depthwise causal convolution with bias, then SiLU (the
    published one convolution over x | B | C, depthwise, is the same);
    dt = softplus(u.w_dt + dt_bias), A = -exp(A_log).  Then per head, B and C
    shared by the heads of a group, a plain sequential scan over positions
    in float32 (the published chunked SSD computes the same recurrence):
    state <- exp(dt A) state + dt x (outer) B, y = state . C + D x.  Then the
    gated RMSNorm of y * silu(z), over each group's share of the inner width
    as the published ``RMSNormGated`` (one group: the whole width), scaled by
    ``ln_gate``; the out projection; the residual at weight ``residual``."""
    s = h.shape[0]
    nh, P, N, G = d["ssm_heads"], d["ssm_head_dim"], d["ssm_state"], d["ssm_groups"]
    u = rms(h, w["ln"], eps)
    z = mm(u, w["w_z"], lower)
    x = jax.nn.silu(_causal_conv(mm(u, w["w_x"], lower), w["conv_x_w"], w["conv_x_b"]))
    B = jax.nn.silu(_causal_conv(mm(u, w["w_B"], lower), w["conv_B_w"], w["conv_B_b"]))
    C = jax.nn.silu(_causal_conv(mm(u, w["w_C"], lower), w["conv_C_w"], w["conv_C_b"]))
    dt = jax.nn.softplus(mm(u, w["w_dt"], lower) + w["dt_bias"])            # (S, nh)
    A = -jnp.exp(w["A_log"])
    x = x.reshape(s, nh, P)
    B = jnp.repeat(B.reshape(s, G, N), nh // G, axis=1)                     # (S, nh, N)
    C = jnp.repeat(C.reshape(s, G, N), nh // G, axis=1)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t,
                                 precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N), jnp.float32), (x, dt, B, C))
    y = (y + w["D"][None, :, None] * x).reshape(s, nh * P)
    g = (y * jax.nn.silu(z)).reshape(s, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return h + residual * mm(g.reshape(s, nh * P) * w["ln_gate"], w["out_proj"], lower)


def _multiplier(ref, name: str, cfg: Dict) -> float:
    return float(getattr(ref, name)(cfg)) if hasattr(ref, name) else 1.0


class Layerwise:
    """Logits of the plain model at chosen positions of whole sequences.

    ``ref`` is the configuration's reference module.  Layer ``li``, of kind
    ``kinds[li]`` (``weights.layer_kinds``), maps h (S, D) float32 to the
    next h by ``ref.layer(h, w, cfg, kind, lower)``, with its weights
    ``ref.layer_weights(key, li, cfg, kind, dtype)`` drawn in the served
    dtype (``torch_dtype``) and cast to float32, its matrix products through
    ``mm(..., lower)``; one program per kind, the kind static and the index
    traced.  The embedding's output is scaled by the reference's
    ``embedding_multiplier`` and the logits by its ``logits_multiplier``.
    ``rows`` is the embedding's row count (the vocabulary as the served
    model pads it), so that the draw matches the served one; logits cover
    the config's vocabulary only.  Every sequence is padded to a multiple of
    ``SEQ_PAD`` (causal layers leave earlier positions alone), so that a few
    programs serve every run of a configuration; none may pass ``max_len``."""

    def __init__(self, ref, cfg: Dict, seed: int, rows: int, max_len: int,
                 control: bool = False):
        self.kinds, self.max_len = W.layer_kinds(ref, cfg), max_len
        lower = None
        if control:
            if cfg["torch_dtype"] not in LOWER:
                raise ValueError(f"no control precision below {cfg['torch_dtype']!r}")
            lower = LOWER[cfg["torch_dtype"]]
        self.key = W.root_key(seed)
        eps, vocab = float(cfg["rms_norm_eps"]), int(cfg["vocab_size"])
        dtype = jnp.dtype(cfg["torch_dtype"])
        to_embed = _multiplier(ref, "embedding_multiplier", cfg)
        to_logits = _multiplier(ref, "logits_multiplier", cfg)

        @jax.jit
        def _embed(key, toks):
            e = W.embed_weights(key, cfg, rows, dtype)[0][toks].astype(jnp.float32)
            return e * to_embed

        @functools.partial(jax.jit, static_argnames="kind")
        def _layer(key, li, h, kind):
            w = {n: a.astype(jnp.float32)
                 for n, a in ref.layer_weights(key, li, cfg, kind, dtype).items()}
            return ref.layer(h, w, cfg, kind, lower)

        @jax.jit
        def _head(key, h, pos):
            _, head, norm = W.embed_weights(key, cfg, rows, dtype)
            x = rms(h[pos], norm.astype(jnp.float32), eps)
            return mm(x, head.astype(jnp.float32)[:, :vocab], lower) * to_logits

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
        for li, kind in enumerate(self.kinds):
            hs = [self._layer(self.key, jnp.int32(li), h, kind=kind) for h in hs]
        out = []
        for h, pos in zip(hs, positions, strict=True):
            at = np.zeros((-(-len(pos) // POS_PAD) * POS_PAD,), np.int32)
            at[: len(pos)] = pos
            out.append(np.asarray(self._head(self.key, h, jnp.asarray(at)))[: len(pos)])
        return out
