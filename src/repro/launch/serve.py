"""Serving entry point: agent episodes on the batched ServingEngine.

Each agent's reasoning steps decode on the engine while its tool calls run on
the host.  While a tool runs, B-PASTE admits the likely follow-up contexts
into free batch slots (``serving/spec_serving.py``), so that the reasoning
after the tool is already decoding when the tool returns.

The model is the architecture's published configuration, random weights from
``--seed``; the number of layers (``--n-layers``) is the only cut.
``--reduced`` takes the architecture's toy preset instead, for the CPU.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --n-layers 16
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced \\
      --prompt-len 48 --max-len 192 --reason-tokens 5

Timings printed on a CPU are the CPU backend's, not a device's.
"""
from __future__ import annotations

import argparse
import dataclasses
import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.events import Event
from repro.core.hypothesis import HypothesisBuilder
from repro.core.patterns import PatternEngine
from repro.core.workload import (
    Episode, WorkloadConfig, episodes_to_traces, make_episodes,
)
from repro.models import model as model_mod
from repro.serving.engine import ServingEngine
from repro.serving.spec_serving import SlotSpeculator, render_observation

# a speculative prompt is the history prompt plus one rendered observation
# (render_observation's default length), so it adds one prefill length
OBS_TOKENS = 16
MAX_TOOL_STEPS = 4       # tool steps served per episode
TOOL_WINDOW = 3          # decode steps a speculative slot gets while a tool runs


def build_config(arch: str, *, n_layers: Optional[int] = None,
                 reduced: bool = False) -> ModelConfig:
    """The published config of ``arch`` (or its toy preset), with the number
    of layers as the only cut."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return cfg


def init_params(cfg: ModelConfig, seed: int):
    """Random parameters in ``cfg.dtype`` from ``seed``, built by one jitted
    program.  The eager ``model.init_params`` materialises every stacked
    float32 draw op by op — several GB beside the resident weights at
    published widths — where XLA fuses each draw into its cast."""
    return jax.jit(model_mod.init_params, static_argnums=1)(
        jax.random.key(seed), cfg)


@dataclass
class ServeReport:
    episodes: int            # agent episodes served to their end
    tokens_decoded: int      # tokens generated, over every active slot
    decode_steps: int        # batched decode steps that ran
    spec_admitted: int       # speculative contexts prefilled into free slots
    promotions: int
    preemptions: int
    compile_s: float         # ahead-of-time compile: decode + each prefill length
    serve_s: float           # wall time of the serving loop
    decode_step_s: float     # median decode step, to block_until_ready
    peak_bytes: Optional[int]  # device peak_bytes_in_use, where reported
    compiled: Dict[str, Any] = field(repr=False, default_factory=dict)


def serve_episodes(
    engine: ServingEngine, episodes: Sequence[Episode],
    pattern_engine: PatternEngine, *, prompt_len: int, reason_tokens: int,
    spec_slots: int = 2, speculate: bool = True, seed: int = 0,
) -> ServeReport:
    """Serve ``episodes`` one after another.  Each gets a random prompt of
    ``prompt_len`` tokens from ``seed``; each of its first
    ``MAX_TOOL_STEPS`` steps decodes ``reason_tokens`` tokens of reasoning,
    then runs its tool.  With ``speculate``, the tool's likely follow-ups
    are prefilled into free slots and decode for ``TOOL_WINDOW`` steps
    while it runs; a slot whose context matches the real observation is
    promoted, the rest are squashed at the episode's end."""
    rng = np.random.default_rng(seed)
    vocab = engine.cfg.vocab_size
    spec = SlotSpeculator(engine, budget_slots=spec_slots)
    builder = HypothesisBuilder(pattern_engine)

    t0 = time.perf_counter()
    lens = [prompt_len] + ([prompt_len + OBS_TOKENS] if speculate else [])
    compiled = engine.compile(lens)
    compile_s = time.perf_counter() - t0

    step_s: List[float] = []
    tokens = 0

    def decode() -> None:
        nonlocal tokens
        t = time.perf_counter()
        out = engine.step()
        jax.block_until_ready(engine.cache)
        if out:
            step_s.append(time.perf_counter() - t)
            tokens += len(out)

    steps_before = engine.steps_executed
    t0 = time.perf_counter()
    for ep in episodes:
        prompt = rng.integers(2, vocab, size=prompt_len).tolist()
        if engine.add_request(prompt, request_id=ep.eid) is None:
            raise RuntimeError("no free slot for an authoritative request")
        history: List[Event] = []
        for step in ep.steps[:MAX_TOOL_STEPS]:
            for _ in range(reason_tokens):
                decode()
            if speculate and history:
                hyps = builder.build(history, beam_width=3)
                spec.admit([(h, h.q) for h in hyps], history_prompt=prompt)
                for _ in range(TOOL_WINDOW):
                    decode()
            if speculate:
                obs = render_observation(step.tool, {}, f"pred:{step.tool}", vocab)
                spec.match_and_promote(obs, ep.eid)
            history.append(Event("tool", step.tool, dict(step.args), {"ok": True}))
        spec.squash_all()
        for s in engine.slots:
            s.active = False
            s.request_id = None
    serve_s = time.perf_counter() - t0

    stats = jax.devices()[0].memory_stats() or {}
    return ServeReport(
        episodes=len(episodes), tokens_decoded=tokens,
        decode_steps=engine.steps_executed - steps_before,
        spec_admitted=spec.admitted, promotions=spec.promotions,
        preemptions=spec.preemptions, compile_s=compile_s, serve_s=serve_s,
        decode_step_s=statistics.median(step_s) if step_s else float("nan"),
        peak_bytes=stats.get("peak_bytes_in_use"), compiled=compiled,
    )


def run(arch: str = "granite-8b", *, n_layers: Optional[int] = None,
        reduced: bool = False, n_episodes: int = 3, max_batch: int = 8,
        max_len: int = 2048, prompt_len: int = 1008, reason_tokens: int = 32,
        spec_slots: int = 2, speculate: bool = True, seed: int = 0,
        ) -> Tuple[ServingEngine, ServeReport]:
    """Build the model and the engine, mine tool patterns from a fixed
    history of episodes, and serve ``n_episodes`` episodes from ``seed``."""
    cfg = build_config(arch, n_layers=n_layers, reduced=reduced)
    engine = ServingEngine(cfg, init_params(cfg, seed),
                           max_batch=max_batch, max_len=max_len)
    history = make_episodes(WorkloadConfig(seed=1, n_episodes=40))
    patterns = PatternEngine(context_len=2, min_support=3).fit(
        episodes_to_traces(history))
    episodes = make_episodes(WorkloadConfig(seed=seed, n_episodes=n_episodes))
    report = serve_episodes(
        engine, episodes, patterns, prompt_len=prompt_len,
        reason_tokens=reason_tokens, spec_slots=spec_slots,
        speculate=speculate, seed=seed)
    return engine, report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the depth to this many layers (widths stay published)")
    ap.add_argument("--reduced", action="store_true",
                    help="the architecture's toy preset, for CPU runs")
    ap.add_argument("--episodes", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--prompt-len", type=int, default=1008)
    ap.add_argument("--reason-tokens", type=int, default=32)
    ap.add_argument("--spec-slots", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    _, r = run(args.arch, n_layers=args.n_layers, reduced=args.reduced,
               n_episodes=args.episodes, max_batch=args.max_batch,
               max_len=args.max_len, prompt_len=args.prompt_len,
               reason_tokens=args.reason_tokens, spec_slots=args.spec_slots,
               seed=args.seed)
    dev = jax.devices()[0]
    print(f"[{dev.platform} {dev.device_kind}] served {r.episodes} episodes: "
          f"{r.tokens_decoded} tokens in {r.decode_steps} decode steps, "
          f"speculative admitted={r.spec_admitted} promoted={r.promotions} "
          f"preempted={r.preemptions}; compile {r.compile_s:.1f}s, "
          f"serve {r.serve_s:.1f}s, median decode step "
          f"{r.decode_step_s * 1e3:.2f} ms, peak bytes {r.peak_bytes}")


if __name__ == "__main__":
    main()
