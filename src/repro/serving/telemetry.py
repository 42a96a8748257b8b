"""Spans and counters of the serving engine, published through
``jax.monitoring``.

A span times a stretch of the engine's host work on ``time.perf_counter``
and is also a ``jax.profiler.TraceAnnotation``, so that a profiler trace
shows it on the device's clock.  When it closes it is published as
``jax.monitoring.record_event_time_span("/repro/<name>", t0, t1, **attrs)``;
a counter as ``jax.monitoring.record_scalar("/repro/<name>", value, **attrs)``.
Attribute values are ``int`` or ``str``.  A span's parent is the span that
holds it on the same thread.

Both are always emitted.  Whoever registers a listener
(``jax.monitoring.register_event_time_span_listener``,
``register_scalar_listener``) keeps them; with none registered a span costs
two clock reads and one TraceMe.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, Union

import jax

PREFIX = "/repro/"

Attr = Union[int, str]


@contextlib.contextmanager
def span(name: str, **attrs: Attr) -> Iterator[None]:
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    jax.monitoring.record_event_time_span(PREFIX + name, t0, time.perf_counter(), **attrs)


def count(name: str, value: int, **attrs: Attr) -> None:
    jax.monitoring.record_scalar(PREFIX + name, value, **attrs)
