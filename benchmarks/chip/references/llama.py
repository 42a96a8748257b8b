"""Plain float32 reference of a llama-style decoder, its seeded weights, and
the work its decode step and prefill need.

The forward pass follows the published llama description: token embedding;
per layer RMSNorm, causal grouped-query attention with rotary positions
(rotate-half form, ``theta`` from the config), residual, RMSNorm, SwiGLU MLP,
residual; final RMSNorm and the LM head.  ``chipbench/reference.py`` holds
the attention block, runs the layers one by one and holds the control.

This module imports nothing of the system under test.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench import flops, reference as R, weights as W


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes the weights, the reference and the work counts read; the
    record's ``dims``."""
    return {
        "L": int(cfg["num_hidden_layers"]), "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]), "KV": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]), "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
    }


def layer_shapes(cfg: Dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    """One layer's leaves in the order that fixes their keys; every layer is
    of one kind."""
    d = dims(cfg)
    D, F = d["D"], d["F"]
    return {**R.attention_shapes(d), "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}


def layer_weights(seed_key, layer, cfg: Dict, kind: str,
                  dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """One layer's leaves; ``layer`` may be traced."""
    return W.layer_draw(seed_key, layer, layer_shapes(cfg, kind), dtype)


def layer(h, w: Dict[str, jnp.ndarray], cfg: Dict, kind: str, lower=None):
    """One decoder layer over one whole sequence. h (S, D) float32."""
    h = R.attention(h, w, dims(cfg), float(cfg["rms_norm_eps"]),
                    float(cfg["rope_theta"]), lower)
    x = R.rms(h, w["ln2"], float(cfg["rms_norm_eps"]))
    g = R.mm(x, w["w_gate"], lower)
    u = R.mm(x, w["w_up"], lower)
    return h + R.mm(jax.nn.silu(g) * u, w["w_down"], lower)


def decode_work(d: Dict[str, int], rows: int, sum_ctx: int) -> Dict:
    """One decode step of ``rows`` active rows whose attended lengths sum to
    ``sum_ctx``: the model's operations, and per Pallas kernel by name its
    operations and bytes (``flops.py``)."""
    return {"model_flops": flops.model_decode(d, rows, sum_ctx),
            "kernels": {"decode_attention": flops.decode_attn(d, rows, sum_ctx)}}


def prefill_work(d: Dict[str, int], n: int) -> Dict:
    """One prefill over ``n`` tokens, as ``decode_work``."""
    return {"model_flops": flops.model_prefill(d, n),
            "kernels": {"flash_attention": flops.flash_prefill(d, n)}}
