"""A small mixture-of-experts decoder as the program's ``moe`` family serves
it: llama's attention, then top-k of E experts, with a capacity that drops
no token (each expert may take every token of a call)."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from chipbench import weights as W


def model_config(cfg: Dict):
    from repro.configs.base import ModelConfig, MoEConfig
    E, k = int(cfg["num_local_experts"]), int(cfg["num_experts_per_tok"])
    F = int(cfg["intermediate_size"])
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), d_ff=F,
        vocab_size=int(cfg["vocab_size"]), head_dim=int(cfg["head_dim"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype=cfg["torch_dtype"],
        attn_impl=cfg.get("attn_impl", "auto"),
        moe=MoEConfig(n_experts=E, top_k=k, d_ff_expert=F, capacity_factor=E / k))


def served_params(cfg: Dict, mcfg, seed: int, ref):
    def layers(stacks):
        b = stacks[W.ONE_KIND]
        return {"blocks": {"ln1": b["ln1"], "ln2": b["ln2"],
                           "attn": {n: b[n] for n in ("wq", "wk", "wv", "wo")},
                           # the program routes in float32
                           "moe": {"router": b["router"].astype(jnp.float32),
                                   **{n: b[n] for n in ("w_gate", "w_up", "w_down")}}}}
    return W.served_tree(ref, cfg, seed, mcfg.padded_vocab, jnp.dtype(mcfg.dtype), layers)
