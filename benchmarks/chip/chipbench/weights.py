"""Seeded random weights, made on the device: the key scheme and the draws
that every architecture shares.

One generator serves both sides of the comparison: the served model gets the
whole tree from one jitted call, and the float32 reference regenerates one
layer at a time from the same seed.  Every leaf of a layer is drawn from its
own key, ``fold_in(fold_in(fold_in(root, 1), layer), leaf)``, where ``layer``
is the layer's position in the reference's ``layer_kinds`` and ``leaf`` the
leaf's position in that kind's ordered shapes (``layer_shapes`` of
``references/<reference>.py``), so a layer drawn alone is bit-identical to
its slice of its kind's stack.  A weight set that several depths apply (a
shared block) is a kind whose ``layer_weights`` draws under one fixed index.

A leaf is drawn by the rule its reference names for it (``rules``: a name to
a function of the key and the shape), else as a norm scale (a name starting
``ln``), else as a projection (two axes or more).  A one-axis leaf that none
of these covers is an error, not a guess.

This module imports nothing of the system under test.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

NORM_JITTER = 0.05
EMBED_STD = 0.02
# the kind of every layer of a reference that gives no ``layer_kinds``
ONE_KIND = "layer"

Rule = Callable[[jax.Array, Tuple[int, ...]], jax.Array]


def layer_kinds(ref, cfg: Dict) -> List[str]:
    """The kind of each layer the reference runs, in order: its
    ``layer_kinds(cfg)``, or ``dims(cfg)["L"]`` layers of ``ONE_KIND``."""
    if hasattr(ref, "layer_kinds"):
        return list(ref.layer_kinds(cfg))
    return [ONE_KIND] * int(ref.dims(cfg)["L"])


def root_key(seed: int):
    """A key from a seed of any size: ``jax.random.key`` keeps only the low
    32 bits, so the high bits are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, name: str, shape, dtype, rules: Dict[str, Rule]):
    """By the leaf's own rule; else a norm scale (a name starting ``ln``) is
    1 + normal(0, NORM_JITTER); else a projection is normal(0, fan_in**-0.5),
    its fan-in the second axis from the end (a stack of experts leads with
    the expert)."""
    if name in rules:
        return rules[name](key, shape).astype(dtype)
    if name.startswith("ln"):
        return (1.0 + NORM_JITTER * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if len(shape) < 2:
        raise ValueError(f"no draw for the leaf {name!r} of shape {tuple(shape)}: "
                         "neither a norm scale nor a projection, and no rule names it")
    return (jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5).astype(dtype)


def layer_draw(seed_key, layer, shapes: Dict[str, Tuple[int, ...]],
               dtype=jnp.bfloat16, rules: Optional[Dict[str, Rule]] = None,
               ) -> Dict[str, jnp.ndarray]:
    """One layer's leaves of the given shapes, in their order; ``layer`` may
    be traced."""
    k = jax.random.fold_in(jax.random.fold_in(seed_key, 1), layer)
    return {n: _draw(jax.random.fold_in(k, i), n, s, dtype, rules or {})
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


def served_tree(ref, cfg: Dict, seed: int, rows: int, dtype,
                layers: Callable[[Dict[str, Dict[str, jnp.ndarray]]], Dict]) -> Dict:
    """The whole served tree in one jitted call on the device.  Each kind's
    layers are drawn by the reference module's ``layer_weights`` over that
    kind's layer indices only, and stacked in their order; ``layers`` takes
    ``{kind: stacked leaves}`` and returns the tree below the embedding in
    the program's layout (``{"blocks": ...}``, and any top-level key beside
    it)."""
    kinds = layer_kinds(ref, cfg)
    at = {k: [i for i, x in enumerate(kinds) if x == k] for k in dict.fromkeys(kinds)}

    @jax.jit
    def make(key):
        stacks = {k: jax.vmap(lambda li, k=k: ref.layer_weights(key, li, cfg, k, dtype))(
            jnp.asarray(ix, jnp.int32)) for k, ix in at.items()}
        embed, head, norm = embed_weights(key, cfg, rows, dtype)
        return {"embed": embed, "final_norm": norm, "lm_head": head, **layers(stacks)}
    return make(root_key(seed))
