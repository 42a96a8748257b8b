"""Plain float32 reference of a small hybrid decoder, Zamba-style as the
program's ``hybrid`` family describes it: a stack of Mamba-2 layers (the
shared ``mamba2`` of ``chipbench/reference.py``), with one attention block
(the shared ``attention``, rotary, no MLP) whose one weight set is applied
after every ``attn_every`` of them; the layers past the last whole group
follow as a tail.  Layers differ in kind (``layer_kinds``), and the shared
block is a kind drawn under one fixed index: the position of its first
application."""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp

from chipbench import flops, reference as R, weights as W

MAMBA, SHARED = "mamba", "shared_attn"


def dims(cfg: Dict) -> Dict[str, int]:
    """``L`` counts the Mamba-2 layers, ``A`` the shared block's
    applications."""
    L, every = int(cfg["num_hidden_layers"]), int(cfg["attn_every"])
    return {
        "L": L, "A": L // every, "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]), "KV": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]), "V": int(cfg["vocab_size"]),
        "ssm_heads": int(cfg["mamba_n_heads"]), "ssm_head_dim": int(cfg["mamba_d_head"]),
        "ssm_state": int(cfg["mamba_d_state"]), "ssm_groups": int(cfg["mamba_n_groups"]),
        "conv": int(cfg["mamba_d_conv"]),
    }


def layer_kinds(cfg: Dict) -> List[str]:
    every = int(cfg["attn_every"])
    kinds = []
    for i in range(int(cfg["num_hidden_layers"])):
        kinds.append(MAMBA)
        if (i + 1) % every == 0:
            kinds.append(SHARED)
    return kinds


def layer_shapes(cfg: Dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    d = dims(cfg)
    if kind == MAMBA:
        return R.mamba2_shapes(d)
    return {n: s for n, s in R.attention_shapes(d).items() if n != "ln2"}


def layer_weights(seed_key, layer, cfg: Dict, kind: str, dtype=jnp.bfloat16):
    if kind == MAMBA:
        return W.layer_draw(seed_key, layer, layer_shapes(cfg, kind), dtype,
                            R.mamba2_rules(dims(cfg)))
    # every application reads the one weight set
    return W.layer_draw(seed_key, layer_kinds(cfg).index(SHARED),
                        layer_shapes(cfg, kind), dtype)


def layer(h, w, cfg, kind, lower=None):
    d, eps = dims(cfg), float(cfg["rms_norm_eps"])
    if kind == MAMBA:
        return R.mamba2(h, w, d, eps, lower)
    return R.attention(h, w, d, eps, float(cfg["rope_theta"]), lower)


def _attention(d: Dict[str, int]) -> Dict[str, int]:
    """The dims ``flops`` counts attention by: one layer per application."""
    return dict(d, L=d["A"])


def _per_token(d: Dict[str, int]) -> int:
    """Operations one token needs in the layers, attention's scores aside: 2
    per weight it meets (Mamba-2's projections and depthwise kernels in
    every layer, the shared block's projections at each application), and
    the recurrence's 5 per state element of each Mamba-2 layer (the update
    exp(dt A) state + dt x (outer) B, the readout state . C)."""
    D, nh, N, K = d["D"], d["ssm_heads"], d["ssm_state"], d["conv"]
    di, gn = nh * d["ssm_head_dim"], d["ssm_groups"] * N
    mamba = D * (2 * di + 2 * gn + nh) + K * (di + 2 * gn) + di * D
    attn = D * (d["H"] + 2 * d["KV"]) * d["hd"] + d["H"] * d["hd"] * D
    return 2 * (d["L"] * mamba + d["A"] * attn) + 5 * d["L"] * di * N


def decode_work(d: Dict[str, int], rows: int, sum_ctx: int) -> Dict:
    att = flops.decode_attn(_attention(d), rows, sum_ctx)
    per_row = _per_token(d) + 2 * d["D"] * d["V"]
    return {"model_flops": float(rows * per_row) + att["flops"],
            "kernels": {"decode_attention": att}}


def prefill_work(d: Dict[str, int], n: int) -> Dict:
    att = flops.flash_prefill(_attention(d), n)
    return {"model_flops": float(n * _per_token(d)) + 2.0 * d["D"] * d["V"] + att["flops"],
            "kernels": {"flash_attention": att}}
