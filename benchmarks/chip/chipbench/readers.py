"""Selections the per-layer metric readers share."""
from __future__ import annotations

from typing import Dict, List, Optional


def in_window(rec: Dict, t: float) -> bool:
    return rec["t0"] <= t < rec["t_end"]


def window_steps(rec: Dict) -> List:
    return [s for s in rec["steps"] if in_window(rec, s[0])]


def window_prefills(rec: Dict) -> List:
    return [p for p in rec["prefills"] if in_window(rec, p[0])]


def traced(rec: Dict, items: List) -> List:
    """The items (t0, t1, ...) that ran wholly inside the traced stretch."""
    a, b = rec.get("trace_window") or (None, None)
    if a is None or b is None:
        return []
    return [x for x in items if a <= x[0] and x[1] <= b]


def kernel_time(rec: Dict, module_prefix: str) -> Optional[float]:
    """Device seconds of the Pallas kernel inside the programs whose name
    starts with ``module_prefix``.  The rule: the two kernels live in
    different programs (decode attention in ``jit__decode``, flash prefill
    in ``jit__prefill_one``), and in each a Pallas kernel is the operation
    whose HLO names ``custom_call_target="tpu_custom_call"``; the program's
    other custom calls are XLA's own buffer allocations."""
    tr = rec.get("trace")
    if not tr:
        return None
    t = sum(x[2] for x in tr["ops"] if x[0].startswith(module_prefix) and x[4])
    return t or None
