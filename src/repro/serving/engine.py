"""Batched decode engine with slot management (continuous batching).

The engine owns a fixed-capacity batched KV/state cache; requests occupy
*slots*.  Free slots are the serving-side analogue of the paper's "slack
resources": B-PASTE admits speculative sequences into them, preempts by
dropping a slot at the next decode-step boundary (one step = the preemption
granularity on an accelerator), and promotes by re-tagging a slot
authoritative — zero-copy, KV rows are slot-stable.

The engine times its own host work with the spans and counters of
``serving/telemetry.py``: ``engine.prefill`` (with ``.write_slot`` and
``.wait``) around ``add_request``, ``engine.decode`` (with ``.prep``,
``.wait`` and ``.emit``) around ``step``, and the counter
``engine.slot_release`` for the rows a speculative slot decoded, counted
where the slot is preempted, promoted or ends itself.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.serving import telemetry


@dataclass
class Slot:
    idx: int
    request_id: Optional[int] = None
    speculative: bool = False
    eu: float = 0.0
    tokens: List[int] = field(default_factory=list)
    active: bool = False
    done: bool = False
    spec_rows: int = 0      # rows decoded while speculative, not yet counted


def _write_slot(cache_tree, slot_cache_tree, slot: int):
    """Write a single-sequence cache into batch position `slot`.

    Batch position differs per leaf: KV leaves are (L, B, S, KV, hd) — batch
    at axis 1; lengths (B,) at axis 0; ssm states (L, B, ...) axis 1."""

    def upd(big, small):
        # the batch axis is the first dim where the batched and the
        # single-sequence cache disagree (1 vs max_batch)
        axis = None
        for i, (b_, s_) in enumerate(zip(big.shape, small.shape, strict=False)):
            if b_ != s_:
                axis = i
                break
        if axis is None:
            return small.astype(big.dtype)
        idx = [slice(None)] * big.ndim
        idx[axis] = slot
        take = [slice(None)] * small.ndim
        take[axis] = 0
        return big.at[tuple(idx)].set(small[tuple(take)].astype(big.dtype))

    return jax.tree.map(upd, cache_tree, slot_cache_tree)


def make_decode(cfg: ModelConfig, mesh_info=None):
    """The engine's decode program: one batched step over every slot.

    ``(params, cache, tokens, active_mask) -> (next_tokens, logits, cache)``.
    The cache is donated and updated in place: each step writes one K/V row
    per slot and nothing else of the cache.  Inactive slots decode too; only
    their ``lengths`` are held back by the mask (see ``ServingEngine``)."""

    def _decode(params, cache, tokens, active_mask):
        logits, new_cache = model_mod.decode_step(params, cfg, cache, tokens, mesh_info)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # inactive slots do not advance
        with jax.named_scope("engine.active_mask"):
            lengths = jnp.where(active_mask, new_cache["lengths"], cache["lengths"])
        return nxt, logits, {**new_cache, "lengths": lengths}

    return jax.jit(_decode, donate_argnums=1)


class ServingEngine:
    """Slots over one batched cache, decoded together one step at a time.

    Dead-slot invariant: the K/V rows and states of an inactive slot are
    never read before they are rewritten.  A slot turns active only in
    ``add_request``, whose ``_write_slot`` rewrites the whole slot, lengths
    included, from the prefill's cache; ``preempt``, a slot finishing, and a
    caller clearing ``Slot.active`` only deactivate.  So the decode step lets
    inactive slots write their one row, at position ``lengths`` (outside the
    slot's valid range, or in a dead window of a rolling buffer), and masks
    only ``lengths``: served tokens of active slots do not depend on it."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_batch: int = 8,
        max_len: int = 1024,
        mesh_info=None,
        eos_id: int = 1,
    ):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.mesh_info = mesh_info
        self.eos_id = eos_id
        self.cache = model_mod.init_cache(cfg, max_batch, max_len)
        self.slots = [Slot(i) for i in range(max_batch)]
        self.pending_tokens = np.zeros((max_batch,), np.int32)
        self.steps_executed = 0

        self._decode = make_decode(cfg, mesh_info)

        @jax.jit
        def _prefill_one(params, tokens):
            # compiles once per prompt length (the shape of `tokens`)
            logits, cache1 = model_mod.prefill(
                params, cfg, {"tokens": tokens}, max_len, mesh_info
            )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, logits, cache1

        self._prefill_one = _prefill_one
        # logits of the last prefill (1, Vp) or decode step (max_batch, Vp),
        # left on the device; read only by correctness checks
        self.last_logits = None

    # ------------------------------------------------------------------
    def compile(self, prompt_lens: Sequence[int]) -> Dict[str, Any]:
        """Compile the decode step and one prefill per prompt length ahead
        of serving; later calls reuse these programs.  Returns the compiled
        programs, keyed ``decode`` and ``prefill_<len>``."""
        b = self.max_batch
        out = {"decode": self._decode.lower(
            self.params, self.cache, jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_)).compile()}
        for n in sorted(set(prompt_lens)):
            out[f"prefill_{n}"] = self._prefill_one.lower(
                self.params, jax.ShapeDtypeStruct((1, n), jnp.int32)).compile()
        return out

    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [s.idx for s in self.slots if not s.active]

    def slack(self) -> int:
        """Idle batch capacity = the engine's slack resource."""
        return len(self.free_slots())

    def add_request(
        self, prompt: List[int], *, request_id: int, speculative: bool = False,
        eu: float = 0.0,
    ) -> Optional[int]:
        free = self.free_slots()
        if not free:
            return None
        slot = free[0]
        s = self.slots[slot]
        s.request_id = request_id
        s.speculative = speculative
        s.eu = eu
        s.tokens = list(prompt)
        s.active = True
        s.done = False
        s.spec_rows = 0
        with telemetry.span("engine.prefill", tokens=len(prompt), speculative=int(speculative),
                            slot=slot, request_id=request_id):
            toks = jnp.asarray([prompt], jnp.int32)
            nxt, self.last_logits, cache1 = self._prefill_one(self.params, toks)
            with telemetry.span("engine.prefill.write_slot"):
                self.cache = _write_slot(self.cache, cache1, slot)
            with telemetry.span("engine.prefill.wait"):
                self.pending_tokens[slot] = int(nxt[0])
        return slot

    def _release(self, s: Slot, outcome: str) -> None:
        """Count the rows a speculative slot decoded, where it leaves."""
        telemetry.count("engine.slot_release", s.spec_rows, outcome=outcome)
        s.spec_rows = 0

    def preempt(self, slot: int):
        """Reclaim a speculative slot at a step boundary (drop, zero-copy)."""
        s = self.slots[slot]
        assert s.speculative, "authoritative slots are never preempted"
        s.active = False
        s.request_id = None
        self._release(s, "preempt")

    def promote(self, slot: int, request_id: int):
        """Speculative -> authoritative (non-preemptible), zero-copy."""
        s = self.slots[slot]
        s.speculative = False
        s.request_id = request_id
        s.eu = float("inf")
        self._release(s, "promote")

    def step(self) -> Dict[int, int]:
        """One batched decode step; returns {slot: new_token} for active slots."""
        live = [s for s in self.slots if s.active and not s.done]
        if not live:
            return {}
        n_spec = sum(1 for s in live if s.speculative)
        with telemetry.span("engine.decode", active=len(live), speculative=n_spec):
            with telemetry.span("engine.decode.prep"):
                active = np.zeros((self.max_batch,), bool)
                active[[s.idx for s in live]] = True
                tokens = jnp.asarray(self.pending_tokens, jnp.int32)
                nxt, self.last_logits, self.cache = self._decode(
                    self.params, self.cache, tokens, jnp.asarray(active))
            with telemetry.span("engine.decode.wait"):
                nxt = np.asarray(nxt)
            with telemetry.span("engine.decode.emit"):
                out: Dict[int, int] = {}
                self.steps_executed += 1
                for s in live:
                    tok = int(nxt[s.idx])
                    s.tokens.append(int(self.pending_tokens[s.idx]))
                    self.pending_tokens[s.idx] = tok
                    out[s.idx] = tok
                    if s.speculative:
                        s.spec_rows += 1
                    if tok == self.eos_id or len(s.tokens) >= self.max_len - 1:
                        s.done = True
                        s.active = False
                        if s.speculative:
                            self._release(s, "done")
        return out
