"""End-to-end metrics of a window, and the comparison that decides
``correct``.

End to end, over every turn that became due in the window (the drain after
the close serves the ones still open):

- ``turn_p90_s``: due to last reasoning token;
- ``ttft_p90_s``: due to first token (slot queueing plus prefill);
- ``itl_p95_ms``: gap between consecutive tokens of one turn;
- ``tokens_per_s``: authoritative tokens produced inside the window over
  its length.

Correctness: a sample of the finished turns, drawn from the seed and holding
the one with most tokens, is run through the float32 reference over prompt
plus served tokens.  At each served token the gap is the reference's best
logit minus the served token's logit; the run's number is the widest gap.
Decoding is greedy, so a served token the reference also ranks first has gap
0, and a wrong cache row, position or mask gives gaps of the order of the
logits' spread.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

SAMPLE_TOKENS = 320     # served tokens the comparison covers, at the least
SAMPLE_MAX_TURNS = 12


def _pct(x: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def counted(rec: Dict) -> List:
    """Turns due inside the window that finished without failing."""
    return [t for t in rec["turns"]
            if rec["t0"] <= t.due < rec["t_end"] and t.end is not None and not t.failed]


def end_to_end(rec: Dict) -> Dict[str, float]:
    turns = counted(rec)
    if not turns:
        return {}
    gaps = [1e3 * (b - a) for t in turns
            for a, b in zip(t.token_times, t.token_times[1:], strict=False)]
    toks = sum(1 for t in rec["turns"] if not t.failed for x in t.token_times
               if rec["t0"] <= x <= rec["t_end"])
    return {
        "turn_p90_s": _pct([t.end - t.due for t in turns], 90),
        "ttft_p90_s": _pct([t.first - t.due for t in turns], 90),
        "itl_p95_ms": _pct(gaps, 95) if gaps else float("nan"),
        "tokens_per_s": toks / rec["seconds"],
    }


def sample(rec: Dict, seed: int) -> List:
    """The finished turn with most served tokens, then others drawn from the
    seed until ``SAMPLE_TOKENS`` served tokens are covered."""
    turns = [t for t in rec["turns"] if t.end is not None and not t.failed]
    if not turns:
        return []
    first = max(turns, key=lambda t: (len(t.tokens), len(t.prompt)))
    rest = [t for t in turns if t is not first]
    order = np.random.default_rng([seed, 0xC4EC]).permutation(len(rest))
    out, n = [first], len(first.tokens)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_TURNS:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def sequences(turns: Sequence) -> Tuple[List[List[int]], List[List[int]]]:
    """Reference inputs: prompt plus served tokens but the last, and the
    positions whose logits predict each served token."""
    seqs = [list(t.prompt) + list(t.tokens[:-1]) for t in turns]
    pos = [list(range(len(t.prompt) - 1, len(t.prompt) - 1 + len(t.tokens))) for t in turns]
    return seqs, pos


def widest_gap(ref_logits: Sequence[np.ndarray], chosen: Sequence[Sequence[int]]) -> float:
    """max over positions of best reference logit minus the chosen token's."""
    g = 0.0
    for lg, toks in zip(ref_logits, chosen, strict=True):
        lg = np.asarray(lg, np.float64)
        idx = np.asarray(toks, np.int64)
        g = max(g, float((lg.max(axis=-1) - lg[np.arange(len(idx)), idx]).max()))
    return g
