"""A llama-style decoder as the program serves it: its ``ModelConfig`` from
the configuration file, and the whole parameter tree in the program's
layout, drawn as ``references/llama.py`` draws it."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from chipbench import weights as W


def model_config(cfg: Dict):
    from repro.configs.base import ModelConfig
    if cfg["attention_bias"] or cfg.get("mlp_bias"):
        raise ValueError(f"{cfg['name']}: the harness builds no bias terms")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]), head_dim=int(cfg["head_dim"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype=cfg["torch_dtype"],
        attn_impl=cfg.get("attn_impl", "auto"))


def served_params(cfg: Dict, mcfg, seed: int, ref):
    """The whole parameter tree in the program's layout, in the served
    dtype, in one jitted call on the device; ``ref`` is the configuration's
    reference module, whose ``layer_weights`` draws each layer."""
    def layers(stacks):
        b = stacks[W.ONE_KIND]
        return {"blocks": {"ln1": b["ln1"], "ln2": b["ln2"],
                           "attn": {n: b[n] for n in ("wq", "wk", "wv", "wo")},
                           "mlp": {n: b[n] for n in ("w_gate", "w_up", "w_down")}}}
    return W.served_tree(ref, cfg, seed, mcfg.padded_vocab, jnp.dtype(mcfg.dtype), layers)
