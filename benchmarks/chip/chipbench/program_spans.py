"""The serving program's own spans and counters, as it publishes them through
``jax.monitoring`` under ``/repro/`` (``repro/serving/telemetry.py``).

One collector, registered once when this module is first imported: the
metric readers import it, so the harness registers it when it loads them,
before the warm-up.  It keeps each span as (name, t0, t1, attrs) with the
program's own ``time.perf_counter`` times, and each counter as (name, t,
value, attrs), stamped with ``time.perf_counter()`` when it arrives; names
lose the prefix.  The readers select by the window on the client's clock,
which is the same, so the warm-up's events drop out.  A program that
publishes nothing leaves the collector empty, and the readers read None.
"""
from __future__ import annotations

import bisect
import time
from typing import Dict, List, Tuple

import jax

from chipbench import readers

PREFIX = "/repro/"

Span = Tuple[str, float, float, Dict]
Count = Tuple[str, float, float, Dict]


class Collector:
    def __init__(self):
        self.spans: List[Span] = []
        self.counts: List[Count] = []

    def on_span(self, event: str, t0: float, t1: float, **attrs) -> None:
        if event.startswith(PREFIX):
            self.spans.append((event[len(PREFIX):], t0, t1, attrs))

    def on_scalar(self, event: str, value: float, **attrs) -> None:
        if event.startswith(PREFIX):
            self.counts.append((event[len(PREFIX):], time.perf_counter(), value, attrs))

    def reset(self) -> None:
        self.spans.clear()
        self.counts.clear()


COLLECTOR = Collector()
jax.monitoring.register_event_time_span_listener(COLLECTOR.on_span)
jax.monitoring.register_scalar_listener(COLLECTOR.on_scalar)


def window_spans(rec: Dict, name: str) -> List[Span]:
    """The spans ``name`` that started in the window."""
    return [s for s in COLLECTOR.spans if s[0] == name and readers.in_window(rec, s[1])]


def window_counts(rec: Dict, name: str) -> List[Count]:
    """The counts ``name`` that arrived in the window."""
    return [c for c in COLLECTOR.counts if c[0] == name and readers.in_window(rec, c[1])]


def child_seconds(parents: List[Span], child: str) -> List[float]:
    """Per parent span, the seconds of the spans ``child`` that lie inside it."""
    kids = sorted((s[1], s[2]) for s in COLLECTOR.spans if s[0] == child)
    starts = [k[0] for k in kids]
    out = []
    for _, t0, t1, _ in parents:
        i = bisect.bisect_left(starts, t0)
        total = 0.0
        while i < len(kids) and kids[i][0] <= t1:
            if kids[i][1] <= t1:
                total += kids[i][1] - kids[i][0]
            i += 1
        out.append(total)
    return out
