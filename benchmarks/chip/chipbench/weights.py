"""Seeded random weights of a llama-style decoder, made on the device.

One generator serves both sides of the comparison: the served model gets the
whole stacked tree from one jitted call, and the float32 reference
regenerates one layer at a time from the same seed.  Every leaf is drawn from
its own key, ``fold_in(fold_in(fold_in(root, 1), layer), leaf)``, so a layer
drawn alone is bit-identical to its slice of the stacked tree.

This module imports nothing of the system under test.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# one layer's leaves; the position of a name fixes its key
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORM_JITTER = 0.05
EMBED_STD = 0.02


def root_key(seed: int):
    """A key from a seed of any size: ``jax.random.key`` keeps only the low
    32 bits, so the high bits are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def dims(cfg: Dict) -> Dict[str, int]:
    return {
        "L": int(cfg["num_hidden_layers"]), "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]), "KV": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]), "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
    }


def layer_shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    d = dims(cfg)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return {
        "ln1": (D,), "ln2": (D,),
        "wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd), "wo": (H * hd, D),
        "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
    }


def _draw(key, name: str, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("ln"):
        return (1.0 + NORM_JITTER * x).astype(dtype)
    return (x * shape[0] ** -0.5).astype(dtype)


def layer_weights(seed_key, layer, cfg: Dict, dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """One layer's leaves; ``layer`` may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(seed_key, 1), layer)
    shapes = layer_shapes(cfg)
    return {n: _draw(jax.random.fold_in(k, i), n, shapes[n], dtype)
            for i, n in enumerate(LAYER_LEAVES)}


def embed_weights(seed_key, cfg: Dict, rows: int, dtype=jnp.bfloat16):
    """(embed (rows, D), lm_head (D, rows), final_norm (D,)); ``rows`` may
    pad the vocabulary, and the padded rows are never read by a token.  With
    ``tie_word_embeddings`` the head is the embedding's transpose."""
    D = dims(cfg)["D"]
    k = jax.random.fold_in(seed_key, 0)
    embed = (jax.random.normal(jax.random.fold_in(k, 0), (rows, D), jnp.float32)
             * EMBED_STD).astype(dtype)
    if cfg["tie_word_embeddings"]:
        head = embed.T
    else:
        head = (jax.random.normal(jax.random.fold_in(k, 1), (D, rows), jnp.float32)
                * EMBED_STD).astype(dtype)
    norm = (1.0 + NORM_JITTER * jax.random.normal(jax.random.fold_in(k, 2), (D,),
                                                  jnp.float32)).astype(dtype)
    return embed, head, norm
