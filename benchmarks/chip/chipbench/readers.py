"""Selections the per-layer metric readers share."""
from __future__ import annotations

import re
from typing import Dict, List, Optional

_SUFFIX = re.compile(r"\.\d+$")


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


def kernel_time(rec: Dict, module_prefix: str, kernel: str) -> Optional[float]:
    """Device seconds of the Pallas kernel named ``kernel`` inside the
    programs whose name starts with ``module_prefix``.  A Pallas kernel is an
    operation whose HLO names ``custom_call_target="tpu_custom_call"`` (the
    program's other custom calls are XLA's own buffer allocations); its
    instruction is named after the kernel, with XLA's ``.<n>`` behind it
    (``decode_attention.4`` in ``jit__decode``)."""
    tr = rec.get("trace")
    if not tr:
        return None
    t = sum(x[2] for x in tr["ops"] if x[0].startswith(module_prefix) and x[4]
            and _SUFFIX.sub("", x[1]) == kernel)
    return t or None
