"""A closed population of ReAct agents, each waiting on its own replies.

Every agent loops over episodes of the frozen roster (``data/roster.json``:
scripted tool calls with the published deterministic tool latencies).  One
turn: the whole history is sent as a fresh request, the engine decodes R
reasoning tokens, the slot is freed, the tool runs on the host clock, and
the observation joins the history.  The history is padded in the
observation up to a multiple of ``bucket`` and cut to its last
``keep_tokens``, so every prompt lands on a bucket.

Steadiness: every seed serves the same work, in another order.  The
scripts are drawn once, from a fixed generator: in each round (the n-th turn
of every agent) the scripts take their reasoning and observation lengths
from the same stratified quantiles of the clipped lognormal, each script
starts on one of the roster's first episodes, and the scripts' first turns
are staggered evenly.  The seed deals the whole scripts to the agents and
draws every token id.  (Lengths dealt anew to each round by the seed would
pair them with other tool latencies, and a closed loop would then do more or
less work in a window from one seed to the next.)
"""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SCRIPTS_SEED = 0x5C817     # the scripts' own generator, the same for every seed


def _quantiles(spec: Dict, n: int) -> np.ndarray:
    """n stratified draws of lognormal(median, sigma), clipped and rounded."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def prompt_lengths(p: Dict) -> List[int]:
    """Every prompt length the traffic can send: the buckets from the first
    prompt up to ``keep_tokens``."""
    return list(range(p["initial_tokens"], p["keep_tokens"] + 1, p["bucket"]))


def load_roster(p: Dict) -> Dict:
    with open(os.path.join(HERE, "..", "data", p["roster"])) as f:
        return json.load(f)


def make_agents(p: Dict, seed: int, roster: Dict) -> List[Dict]:
    """Per agent: a start offset, a first episode, and per round its
    reasoning and observation lengths.  Tools come from the roster as the
    agent walks it.  The scripts are the same for every seed; the seed deals
    them to the agents."""
    rng = np.random.default_rng(SCRIPTS_SEED)
    n, rounds = p["agents"], p["rounds"]
    r_set = _quantiles(p["reason_tokens"], n)
    o_set = _quantiles(p["observation_tokens"], n)
    r = np.stack([rng.permutation(r_set) for _ in range(rounds)], axis=1)
    o = np.stack([rng.permutation(o_set) for _ in range(rounds)], axis=1)
    starts = rng.permutation(n) % len(roster["episodes"])
    scripts = [{"episode0": int(starts[i]), "delay_s": i * p["stagger_s"],
                "reason": [int(x) for x in r[i]], "obs": [int(x) for x in o[i]]}
               for i in range(n)]
    deal = np.random.default_rng([seed, 0x5EED]).permutation(n)
    return [dict(scripts[deal[a]], agent=a) for a in range(n)]


def token_stream(seed: int, agent: int, vocab: int):
    """The agent's own source of token ids in [2, vocab)."""
    rng = np.random.default_rng([seed, 0x70C, agent])

    def draw(k: int) -> List[int]:
        return rng.integers(2, vocab, size=k).tolist()
    return draw


def pad_history(hist: List[int], p: Dict, draw) -> List[int]:
    """Pad to the next multiple of the bucket, then keep the last tokens."""
    b = p["bucket"]
    target = max(p["initial_tokens"], math.ceil(len(hist) / b) * b)
    hist = hist + draw(target - len(hist))
    return hist[-p["keep_tokens"]:]
