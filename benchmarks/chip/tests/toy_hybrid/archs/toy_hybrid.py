"""A small hybrid decoder as the program's ``hybrid`` family serves it:
Mamba-2 layers stacked in ``blocks``, and one shared attention block,
``shared_attn``, applied after every ``attn_every`` of them."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from chipbench import weights as W

SSM_LEAVES = ("w_z", "w_x", "w_B", "w_C", "w_dt", "conv_x_w", "conv_x_b", "conv_B_w",
              "conv_B_b", "conv_C_w", "conv_C_b", "out_proj")


def model_config(cfg: Dict):
    from repro.configs.base import ModelConfig, SSMConfig
    D, expand, P = int(cfg["hidden_size"]), int(cfg["mamba_expand"]), int(cfg["mamba_d_head"])
    if expand * D != int(cfg["mamba_n_heads"]) * P:
        raise ValueError(f"{cfg['name']}: the program sizes Mamba-2 by mamba_expand, "
                         f"and {expand} * {D} is not mamba_n_heads * mamba_d_head")
    return ModelConfig(
        name=cfg["name"], family="hybrid", n_layers=int(cfg["num_hidden_layers"]),
        d_model=D, n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]), head_dim=int(cfg["head_dim"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype=cfg["torch_dtype"],
        attn_impl=cfg.get("attn_impl", "auto"), attn_every=int(cfg["attn_every"]),
        ssm=SSMConfig(d_state=int(cfg["mamba_d_state"]), d_conv=int(cfg["mamba_d_conv"]),
                      expand=expand, n_groups=int(cfg["mamba_n_groups"]), head_dim=P,
                      chunk=int(cfg["mamba_chunk_size"])))


def served_params(cfg: Dict, mcfg, seed: int, ref):
    def layers(stacks):
        m, a = stacks["mamba"], stacks["shared_attn"]
        ssm = {n: m[n] for n in SSM_LEAVES}
        # the program holds the per-head scalars in float32
        ssm.update({n: m[n].astype(jnp.float32) for n in ("A_log", "D", "dt_bias")},
                   norm_w=m["ln_gate"])
        # each application drew the same weights: the program holds one copy
        return {"blocks": {"ln": m["ln"], "ssm": ssm},
                "shared_attn": {"ln": a["ln1"][0],
                                "attn": {n: a[n][0] for n in ("wq", "wk", "wv", "wo")}}}
    return W.served_tree(ref, cfg, seed, mcfg.padded_vocab, jnp.dtype(mcfg.dtype), layers)
