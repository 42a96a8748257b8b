"""Host spans around the calls the client makes into each layer, and a
count of the compilations that start inside a window.

A span is (name, start, end, depth) on the host clock.  With ``annotate``
each span is also a ``jax.profiler.TraceAnnotation``, so that it lands in the
profiler's trace on the same clock as the device's operations.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Tuple

import jax

Span = Tuple[str, float, float, int]

# monitoring events that mark a program being lowered or compiled (a
# persistent-cache hit still lowers), so that none may fire in the window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Spans:
    def __init__(self, annotate: bool = False, clock: Callable[[], float] = time.perf_counter):
        self.spans: List[Span] = []
        self.annotate = annotate
        self.clock = clock
        self._depth = 0

    @contextlib.contextmanager
    def span(self, name: str):
        ann = jax.profiler.TraceAnnotation(name) if self.annotate else contextlib.nullcontext()
        t0 = self.clock()
        self._depth += 1
        try:
            with ann:
                yield
        finally:
            self._depth -= 1
            self.spans.append((name, t0, self.clock(), self._depth))


class CompileCounter:
    """Counts lowering and compile events while ``armed``."""

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name in COMPILE_EVENTS:
            self.count += 1
