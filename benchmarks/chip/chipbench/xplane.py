"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

From each TPU device plane it takes the "XLA Ops" line (one event per
operation run) and the "XLA Modules" line (one event per program run), and
from the host plane the spans the benchmark annotated.  Within the traced
window, marked by the host span ``WINDOW``, it gives:

- ``busy_s``: the union of the intervals in which an operation ran, averaged
  over the device planes;
- ``ops``: per (program, operation) the device seconds, the run count and
  whether it is a Pallas kernel, where the program is the module event the
  operation lies in and the operation is the HLO instruction's name (the
  trace names an operation by its whole HLO text).  Operations that hold
  others (a ``while`` over the layers) are left out of ``ops``, so that
  nothing is counted twice; they still count as busy;
- ``modules``: per program the device seconds and run count;
- ``gaps``: the idle time between operations, summed by the innermost host
  span that holds the gap's middle (``(none)`` outside every span).

All times are seconds.  Names keep the program's own, with the trailing
run id of a module (``jit__decode(12)``) cut off.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.traced_window"
_RUN_ID = re.compile(r"\(\d+\)$")
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
PALLAS = 'custom_call_target="tpu_custom_call"'


def _module_name(name: str) -> str:
    return _RUN_ID.sub("", name)


def _instruction(text: str) -> str:
    m = _INSTR.match(text)
    return m.group(1) if m else text[:64]


def _containers(events) -> set:
    """Indices of events that hold a later event of the same line."""
    out, stack = set(), []
    order = sorted(range(len(events)), key=lambda i: (events[i].start_ns, -events[i].end_ns))
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            out.add(stack[-1])
        stack.append(i)
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "spec.", "hyp.", "client.", "bench.")):
                    spans.append((e.start_ns, e.end_ns, e.name))
    return spans


def reduce(path: str) -> Optional[Dict]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host = _host_spans(pd)
    win = [s for s in host if s[2] == WINDOW]
    if not win:
        raise ValueError(f"{path}: no {WINDOW} span on the host")
    w0, w1 = win[0][0], win[0][1]
    inner = sorted((s for s in host if s[2] != WINDOW), key=lambda s: s[0])

    ops: Dict[Tuple[str, str], List[float]] = {}
    modules: Dict[str, List[float]] = {}
    busy_total, n_dev, gaps_all = 0.0, 0, []
    for plane in pd.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        op_ev = [e for e in lines.get("XLA Ops", []) if e.end_ns > w0 and e.start_ns < w1]
        holders = _containers(op_ev)
        async_ev = [e for e in lines.get("Async XLA Ops", [])
                    if e.end_ns > w0 and e.start_ns < w1]
        mod_ev = sorted(((e.start_ns, e.end_ns, _module_name(e.name))
                         for e in lines.get("XLA Modules", [])
                         if e.end_ns > w0 and e.start_ns < w1))
        if not op_ev and not mod_ev:
            continue
        n_dev += 1
        starts = [m[0] for m in mod_ev]
        for s0, s1, name in mod_ev:
            rec = modules.setdefault(name, [0.0, 0])
            rec[0] += (min(s1, w1) - max(s0, w0)) * 1e-9
            rec[1] += 1
        ivs = [(max(e.start_ns, w0), min(e.end_ns, w1)) for e in op_ev + async_ev]
        for k, e in enumerate(op_ev):
            if k in holders:
                continue
            a, b = ivs[k]
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = mod_ev[i][2] if i >= 0 and mod_ev[i][1] >= e.start_ns else "(none)"
            rec = ops.setdefault((mod, _instruction(e.name)), [0.0, 0, PALLAS in e.name])
            rec[0] += (b - a) * 1e-9
            rec[1] += 1
        busy = _union(ivs)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps_all += [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                     if edges[k + 1] > edges[k]]
    if not n_dev:
        return None     # no TPU plane (a CPU run): nothing for the device metrics

    gaps: Dict[str, float] = {}
    span_starts = [s[0] for s in inner]
    for a, b in gaps_all:
        mid = (a + b) / 2
        name, best = "(none)", None
        # spans nest a few deep: the holder of ``mid`` starts among the last few
        for s0, s1, n in inner[max(0, bisect.bisect_right(span_starts, mid) - 8):
                               bisect.bisect_right(span_starts, mid)]:
            if s1 >= mid and (best is None or s1 - s0 < best):
                best, name = s1 - s0, n
        gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9 / n_dev
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n_dev,
        "devices": n_dev,
        "ops": [[m, o, t, c, k] for (m, o), (t, c, k) in sorted(ops.items())],
        "modules": {m: [t, c] for m, (t, c) in sorted(modules.items())},
        "gaps": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
    }


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The contract's ``breakdown``: the operations that took most device
    time, and the idle time by what the host was doing."""
    per_op: Dict[str, float] = {}
    for m, o, t, _, _ in red["ops"]:
        per_op[f"{m}/{o}"] = per_op.get(f"{m}/{o}", 0.0) + t
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = list(red["gaps"].items())[:top]
    return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": [[n, t] for n, t in gaps]}
