"""Seeded random weights, made on the device: the key scheme and the draws
that every architecture shares.

One generator serves both sides of the comparison: the served model gets the
whole stacked tree from one jitted call, and the float32 reference
regenerates one layer at a time from the same seed.  Every leaf of a layer is
drawn from its own key, ``fold_in(fold_in(fold_in(root, 1), layer), leaf)``,
where ``leaf`` is the leaf's position in its architecture's ordered shapes
(``layer_shapes`` of ``references/<reference>.py``), so a layer drawn alone
is bit-identical to its slice of the stacked tree.

This module imports nothing of the system under test.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

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


def _draw(key, name: str, shape, dtype):
    """A norm scale (a name starting ``ln``) is 1 + normal(0, NORM_JITTER);
    a projection is normal(0, fan_in**-0.5), its fan-in the second axis from
    the end (a stack of experts leads with the expert)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("ln"):
        return (1.0 + NORM_JITTER * x).astype(dtype)
    return (x * shape[-2] ** -0.5).astype(dtype)


def layer_draw(seed_key, layer, shapes: Dict[str, Tuple[int, ...]],
               dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """One layer's leaves of the given shapes, in their order; ``layer`` may
    be traced."""
    k = jax.random.fold_in(jax.random.fold_in(seed_key, 1), layer)
    return {n: _draw(jax.random.fold_in(k, i), n, s, dtype)
            for i, (n, s) in enumerate(shapes.items())}


def embed_weights(seed_key, cfg: Dict, rows: int, dtype=jnp.bfloat16):
    """(embed (rows, D), lm_head (D, rows), final_norm (D,)); ``rows`` may
    pad the vocabulary, and the padded rows are never read by a token.  With
    ``tie_word_embeddings`` the head is the embedding's transpose."""
    D = int(cfg["hidden_size"])
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


def served_tree(ref, cfg: Dict, seed: int, n_layers: int, rows: int, dtype,
                blocks: Callable[[Dict[str, jnp.ndarray]], Dict]) -> Dict:
    """The whole served tree in one jitted call on the device: every layer
    drawn by the reference module's ``layer_weights`` and stacked, and
    ``blocks`` putting the stacked leaves in the program's layout."""
    @jax.jit
    def make(key):
        stacked = jax.vmap(lambda li: ref.layer_weights(key, li, cfg, dtype))(
            jnp.arange(n_layers))
        embed, head, norm = embed_weights(key, cfg, rows, dtype)
        return {"embed": embed, "final_norm": norm, "lm_head": head,
                "blocks": blocks(stacked)}
    return make(root_key(seed))
