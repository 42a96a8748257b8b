"""The system under test, built through the program's public API: the
configuration's architecture (``archs/<architecture>.py``) gives its
``ModelConfig`` and its weights, drawn as the configuration's reference
draws them; then a ``ServingEngine`` over them, a ``SlotSpeculator`` and a
``HypothesisBuilder`` over a ``PatternEngine`` mined from the roster's frozen
traces."""
from __future__ import annotations

from typing import Dict, List

# an id no token takes: argmax never returns it, so R is set by the traffic
NO_EOS = -1


def mining_traces(roster: Dict) -> List[list]:
    from repro.core.events import Event
    return [[Event("tool", e["tool"], dict(e["args"]), e["result"]) for e in tr]
            for tr in roster["mining_traces"]]


def build(cfg: Dict, seed: int, roster: Dict, arch, ref):
    """(engine, speculator, hypothesis builder), weights on the device;
    ``arch`` and ``ref`` are the configuration's architecture and reference
    modules."""
    from repro.core.hypothesis import HypothesisBuilder
    from repro.core.patterns import PatternEngine
    from repro.serving.engine import ServingEngine
    from repro.serving.spec_serving import SlotSpeculator

    mcfg = arch.model_config(cfg)
    eng = cfg["engine"]
    params = arch.served_params(cfg, mcfg, seed, ref)
    engine = ServingEngine(mcfg, params, max_batch=eng["max_batch"],
                           max_len=eng["max_len"], eos_id=NO_EOS)
    spec = SlotSpeculator(engine, budget_slots=eng["spec_slots"])
    patterns = PatternEngine(context_len=2, min_support=3).fit(mining_traces(roster))
    return engine, spec, HypothesisBuilder(patterns)
