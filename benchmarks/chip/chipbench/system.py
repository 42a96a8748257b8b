"""The system under test, built from a configuration file through the
program's public API: its ``ModelConfig``, a ``ServingEngine`` over weights
made by ``weights.py``, a ``SlotSpeculator`` and a ``HypothesisBuilder`` over
a ``PatternEngine`` mined from the roster's frozen traces."""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from chipbench import weights as W

# an id no token takes: argmax never returns it, so R is set by the traffic
NO_EOS = -1


def model_config(cfg: Dict):
    from repro.configs.base import ModelConfig
    if cfg.get("architecture") != "llama":
        raise ValueError(f"{cfg['name']}: architecture {cfg.get('architecture')!r} "
                         "is not one this harness builds")
    if cfg["attention_bias"] or cfg.get("mlp_bias"):
        raise ValueError(f"{cfg['name']}: the harness builds no bias terms")
    d = W.dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=d["L"], d_model=d["D"],
        n_heads=d["H"], n_kv_heads=d["KV"], d_ff=d["F"], vocab_size=d["V"],
        head_dim=d["hd"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype=cfg["torch_dtype"],
        attn_impl=cfg.get("attn_impl", "auto"))


def served_params(cfg: Dict, mcfg, seed: int):
    """The whole parameter tree in the program's layout, bf16, in one jitted
    call on the device."""
    dtype = jnp.dtype(mcfg.dtype)
    L = mcfg.n_layers

    @jax.jit
    def make(key):
        blocks = jax.vmap(lambda li: W.layer_weights(key, li, cfg, dtype))(jnp.arange(L))
        embed, head, norm = W.embed_weights(key, cfg, mcfg.padded_vocab, dtype)
        return {
            "embed": embed, "final_norm": norm, "lm_head": head,
            "blocks": {
                "ln1": blocks["ln1"], "ln2": blocks["ln2"],
                "attn": {n: blocks[n] for n in ("wq", "wk", "wv", "wo")},
                "mlp": {n: blocks[n] for n in ("w_gate", "w_up", "w_down")},
            },
        }
    return make(W.root_key(seed))


def mining_traces(roster: Dict) -> List[list]:
    from repro.core.events import Event
    return [[Event("tool", e["tool"], dict(e["args"]), e["result"]) for e in tr]
            for tr in roster["mining_traces"]]


def build(cfg: Dict, seed: int, roster: Dict):
    """(engine, speculator, hypothesis builder), weights on the device."""
    from repro.core.hypothesis import HypothesisBuilder
    from repro.core.patterns import PatternEngine
    from repro.serving.engine import ServingEngine
    from repro.serving.spec_serving import SlotSpeculator

    mcfg = model_config(cfg)
    eng = cfg["engine"]
    params = served_params(cfg, mcfg, seed)
    engine = ServingEngine(mcfg, params, max_batch=eng["max_batch"],
                           max_len=eng["max_len"], eos_id=NO_EOS)
    spec = SlotSpeculator(engine, budget_slots=eng["spec_slots"])
    patterns = PatternEngine(context_len=2, min_support=3).fit(mining_traces(roster))
    return engine, spec, HypothesisBuilder(patterns)

