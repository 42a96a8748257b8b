"""Plain float32 reference of a small mixture-of-experts decoder: the shared
attention block (``chipbench/reference.py``), then RMSNorm and an MLP of E
SwiGLU experts, of which each token takes the k with the highest softmax
router probability, weighted by those probabilities renormalised over the k;
a loop over the experts."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench import flops, reference as R, weights as W


def dims(cfg: Dict) -> Dict[str, int]:
    return {
        "L": int(cfg["num_hidden_layers"]), "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]), "KV": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]), "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]), "E": int(cfg["num_local_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
    }


def layer_shapes(cfg: Dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    d = dims(cfg)
    D, F, E = d["D"], d["F"], d["E"]
    return {**R.attention_shapes(d), "router": (D, E),
            "w_gate": (E, D, F), "w_up": (E, D, F), "w_down": (E, F, D)}


def layer_weights(seed_key, layer, cfg: Dict, kind: str, dtype=jnp.bfloat16):
    return W.layer_draw(seed_key, layer, layer_shapes(cfg, kind), dtype)


def layer(h, w, cfg, kind, lower=None):
    d, eps = dims(cfg), float(cfg["rms_norm_eps"])
    h = R.attention(h, w, d, eps, float(cfg["rope_theta"]), lower)
    x = R.rms(h, w["ln2"], eps)
    top, idx = jax.lax.top_k(jax.nn.softmax(R.mm(x, w["router"], lower), axis=-1), d["k"])
    top = top / top.sum(axis=-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(d["E"]):
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1, keepdims=True)
        g = R.mm(x, w["w_gate"][e], lower)
        u = R.mm(x, w["w_up"][e], lower)
        y = y + gate * R.mm(jax.nn.silu(g) * u, w["w_down"][e], lower)
    return h + y


def _active_params(d: Dict[str, int]) -> int:
    """Weights one token meets in one layer: attention, the router, k experts."""
    D, H, KV, hd = d["D"], d["H"], d["KV"], d["hd"]
    return D * (H + 2 * KV) * hd + H * hd * D + D * d["E"] + d["k"] * 3 * D * d["F"]


def decode_work(d: Dict[str, int], rows: int, sum_ctx: int) -> Dict:
    att = flops.decode_attn(d, rows, sum_ctx)
    per_row = 2 * (d["L"] * _active_params(d) + d["D"] * d["V"])
    return {"model_flops": float(rows * per_row) + att["flops"],
            "kernels": {"decode_attention": att}}


def prefill_work(d: Dict[str, int], n: int) -> Dict:
    att = flops.flash_prefill(d, n)
    return {"model_flops": 2.0 * n * d["L"] * _active_params(d) + 2.0 * d["D"] * d["V"]
            + att["flops"], "kernels": {"flash_attention": att}}
