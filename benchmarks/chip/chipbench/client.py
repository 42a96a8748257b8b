"""The agent client: a closed population of ReAct agents interleaved on one
serving engine, with their tools on the host clock.

It calls only the program's serving API, the calls ``serve_episodes`` makes:
``ServingEngine.add_request`` / ``step`` / ``preempt`` / ``slack``, and
``SlotSpeculator.admit`` / ``match_and_promote`` /
``ensure_authoritative_room``, with ``HypothesisBuilder.build``.  A turn:

1. becomes due (the agent's tool ended, or its first turn's stagger passed);
2. waits for a slot (``ensure_authoritative_room(1)``, else a FIFO queue) and
   sends the whole history as a fresh request;
3. decodes R reasoning tokens, then its slot is freed;
4. its tool runs for the roster's latency; while it runs, the hypotheses
   built from the episode's earlier tools are admitted into free slots;
5. when the tool ends, a speculative slot that decoded from this exact
   observation is promoted, the agent's others are dropped, and the next
   turn is due.

Every call into a layer runs inside a host span.  After the window closes no
turn becomes due; the turns already due are served to their end (at most
``DRAIN_S``), so that a tail holds every request the window sent.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

DRAIN_S = 60.0


@dataclass
class Turn:
    agent: int
    rid: int
    due: float
    prompt: List[int]
    reason: int
    admitted: Optional[float] = None
    first: Optional[float] = None
    end: Optional[float] = None
    slot: Optional[int] = None
    tokens: List[int] = field(default_factory=list)
    token_times: List[float] = field(default_factory=list)
    promoted: bool = False
    failed: bool = False


@dataclass
class Agent:
    idx: int
    script: Dict
    draw: object
    episode: int
    step: int = 0
    round: int = 0
    history: List[int] = field(default_factory=list)
    events: list = field(default_factory=list)
    state: str = "waiting"      # waiting | queued | decoding | tool | done
    wake: float = 0.0           # when the pending turn is due
    turn: Optional[Turn] = None
    spec: List[Tuple[int, object, List[int]]] = field(default_factory=list)


class AgentClient:
    def __init__(self, engine, spec, builder, gen, traffic: Dict, scripts: List[Dict],
                 roster: Dict, draws, spans, clock=time.perf_counter, sleep=time.sleep):
        from repro.core.events import Event
        self._event = Event
        self.engine, self.spec, self.builder, self.gen = engine, spec, builder, gen
        self.p = traffic
        self.roster = roster["episodes"]
        self.spans = spans
        self.clock, self.sleep = clock, sleep
        self.agents = [Agent(s["agent"], s, draws[s["agent"]], s["episode0"])
                       for s in scripts]
        self.turns: List[Turn] = []
        self.steps: List[Tuple[float, float, int, int, int]] = []   # t0 t1 active spec sum_ctx
        self.prefills: List[Tuple[float, float, int, bool]] = []     # t0 t1 tokens speculative
        self._queue: collections.deque = collections.deque()
        self._by_slot: Dict[int, Turn] = {}
        self._rid = 0
        self.spec_dropped = 0
        self._wrap_add_request()

    # -- engine calls -----------------------------------------------------
    def _wrap_add_request(self) -> None:
        """Span every prefill, the speculator's too, and wait in it for the
        device, so that the span holds the prefill and the slot write."""
        eng, inner = self.engine, self.engine.add_request

        def add_request(prompt, **kw):
            t0 = self.clock()
            with self.spans.span("engine.add_request"):
                slot = inner(prompt, **kw)
                jax.block_until_ready(eng.cache)
            if slot is not None:
                self.prefills.append((t0, self.clock(), len(prompt),
                                      bool(kw.get("speculative"))))
            return slot
        eng.add_request = add_request

    def _step(self) -> Dict[int, int]:
        eng = self.engine
        live = [s for s in eng.slots if s.active and not s.done]
        n_spec = sum(1 for s in live if s.speculative)
        ctx = sum(len(s.tokens) + 1 for s in live)
        t0 = self.clock()
        with self.spans.span("engine.step"):
            out = eng.step()
            jax.block_until_ready(eng.cache)
        t1 = self.clock()
        self.steps.append((t0, t1, len(live), n_spec, ctx))
        # a speculative slot that ran into max_len freed itself
        for slot in [s for s in self.spec.active if not eng.slots[s].active]:
            del self.spec.active[slot]
        for slot, tok in out.items():
            turn = self._by_slot.get(slot)
            if turn is not None:
                turn.tokens.append(tok)
                turn.token_times.append(t1)
        return out

    # -- turns ------------------------------------------------------------
    def _roster_step(self, a: Agent) -> Dict:
        return self.roster[a.episode % len(self.roster)]["steps"][a.step]

    def _new_turn(self, a: Agent, due: float) -> None:
        self._rid += 1
        r = a.script["reason"][a.round % len(a.script["reason"])]
        a.turn = Turn(a.idx, self._rid, due, list(a.history), r)
        a.state = "queued"
        self._queue.append(a)

    def _admit(self, a: Agent) -> None:
        t = a.turn
        now = self.clock()
        t.admitted = now
        try:
            slot = self.engine.add_request(t.prompt, request_id=t.rid)
        except Exception:
            t.failed, t.end = True, self.clock()
            self.turns.append(t)
            self._start_tool(a, speculate=False)
            return
        t.slot = slot
        t.tokens.append(int(self.engine.pending_tokens[slot]))
        t.first = self.clock()
        t.token_times.append(t.first)
        self._by_slot[slot] = t
        a.state = "decoding"

    def _finish(self, a: Agent, closing: bool) -> None:
        t = a.turn
        t.end = t.token_times[t.reason - 1]
        del t.tokens[t.reason:], t.token_times[t.reason:]
        s = self.engine.slots[t.slot]
        s.active = False
        s.request_id = None
        del self._by_slot[t.slot]
        self.turns.append(t)
        self._start_tool(a, speculate=not closing)

    def _start_tool(self, a: Agent, speculate: bool) -> None:
        t = a.turn
        step = self._roster_step(a)
        a.wake = t.end + step["latency_s"] * self.p["tool_latency_scale"]
        a.state = "tool"
        a.spec = []
        if not (speculate and a.events):
            return
        n_obs = self.p["spec_obs_tokens"]
        base = (t.prompt + t.tokens)[-(len(t.prompt) - n_obs):]
        with self.spans.span("hyp.build"):
            hyps = self.builder.build(a.events, beam_width=self.p["spec_beam_width"])
        before = dict(self.spec.active)
        with self.spans.span("spec.admit"):
            self.spec.admit([(h, h.q) for h in hyps], history_prompt=base)
        a.spec = [(s, seq, base) for s, seq in self.spec.active.items()
                  if before.get(s) is not seq]

    def _tool_end(self, a: Agent, closing: bool) -> None:
        t = a.turn
        step = self._roster_step(a)
        obs = a.draw(a.script["obs"][a.round % len(a.script["obs"])])
        n_obs = self.p["spec_obs_tokens"]
        with self.spans.span("spec.match_and_promote"):
            hit = self.spec.match_and_promote(obs[:n_obs], t.rid + 1)
        with self.spans.span("spec.drop"):
            for slot, seq, _ in a.spec:
                if slot != hit and self.spec.active.get(slot) is seq:
                    self.engine.preempt(slot)
                    del self.spec.active[slot]
                    self.spec.preemptions += 1
                    self.spec_dropped += 1
        a.events.append(self._event("tool", step["tool"], dict(step["args"]), {"ok": True}))
        a.round += 1
        a.step += 1
        if a.step >= len(self.roster[a.episode % len(self.roster)]["steps"]):
            a.episode, a.step, a.events = a.episode + 1, 0, []
            a.history = a.draw(self.p["initial_tokens"])
        else:
            a.history = self.gen.pad_history(t.prompt + t.tokens + obs, self.p, a.draw)
        due = a.wake
        if closing:
            a.state = "done"
            return
        self._new_turn(a, due)
        if hit is not None:
            self._take_promoted(a, hit, next(b for s, _, b in a.spec if s == hit))

    def _take_promoted(self, a: Agent, slot: int, base: List[int]) -> None:
        """The promoted slot already holds this turn's reasoning."""
        self._queue.remove(a)
        t = a.turn
        s = self.engine.slots[slot]
        n = len(base) + self.p["spec_obs_tokens"]
        t.prompt, t.promoted, t.slot = list(s.tokens[:n]), True, slot
        t.admitted = t.first = self.clock()
        t.tokens = list(s.tokens[n:]) + [int(self.engine.pending_tokens[slot])]
        t.token_times = [t.first] * len(t.tokens)
        self._by_slot[slot] = t
        a.state = "decoding"

    # -- the loop ---------------------------------------------------------
    def run(self, seconds: float, on_tick=None) -> Dict:
        """Serve for ``seconds``, then drain.  ``on_tick(now)`` runs once a
        loop iteration (the traced run starts and stops the profiler there)."""
        t0 = self.clock()
        t_end = t0 + seconds
        for a in self.agents:
            a.history = a.draw(self.p["initial_tokens"])
            a.wake = t0 + a.script["delay_s"]
            a.state = "waiting"
        drain_end = None
        while True:
            now = self.clock()
            if on_tick is not None:
                on_tick(now)
            closing = now >= t_end
            if closing and drain_end is None:
                drain_end = now + DRAIN_S
            for a in self.agents:
                if a.state == "waiting" and a.wake <= now:
                    if a.wake < t_end:
                        self._new_turn(a, a.wake)
                    else:
                        a.state = "done"
                elif a.state == "tool" and a.wake <= now:
                    self._tool_end(a, closing=a.wake >= t_end)
            while self._queue:
                if self.engine.slack() == 0:
                    with self.spans.span("spec.ensure_authoritative_room"):
                        self.spec.ensure_authoritative_room(1)
                if self.engine.slack() == 0:
                    break
                self._admit(self._queue.popleft())
            pending = self._queue or self._by_slot
            if closing and (not pending or now >= drain_end):
                break
            if any(s.active and not s.done for s in self.engine.slots):
                self._step()
                for t in [t for t in self._by_slot.values() if len(t.tokens) >= t.reason]:
                    self._finish(self.agents[t.agent], closing=self.clock() >= t_end)
            else:
                wakes = [a.wake for a in self.agents if a.state in ("waiting", "tool")]
                until = min(wakes + [t_end]) if not closing else min(wakes + [drain_end])
                with self.spans.span("client.idle"):
                    self.sleep(max(0.0, until - self.clock()))
        return {"t0": t0, "t_end": t_end, "seconds": seconds,
                "turns": self.turns, "steps": self.steps, "prefills": self.prefills,
                "spans": self.spans.spans, "spec_admitted": self.spec.admitted,
                "spec_promoted": self.spec.promotions,
                "spec_preempted": self.spec.preemptions, "spec_dropped": self.spec_dropped,
                "unfinished": sum(1 for a in self.agents if a.turn is not None
                                  and a.turn.end is None and a.turn.due < t_end)}
