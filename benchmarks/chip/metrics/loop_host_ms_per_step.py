"""Host time of the agent loop outside the engine, per decode step (ms).

The window's wall time less the spans ``engine.add_request``,
``engine.step``, ``client.idle`` and, in the traced run, the profiler's
start and stop ``bench.profiler`` (which do not nest in one another),
over the decode steps that started in the window.  What is left is the
client's own bookkeeping, ``HypothesisBuilder.build``, the speculator's
admission, matching and dropping, and the loop itself.
"""
from chipbench import readers

ENGINE = ("engine.add_request", "engine.step", "client.idle", "bench.profiler")


def read(rec):
    t0, t1 = rec["t0"], rec["t_end"]
    steps = readers.window_steps(rec)
    if not steps:
        return None
    inside = sum(max(0.0, min(b, t1) - max(a, t0))
                 for n, a, b, _ in rec["spans"] if n in ENGINE)
    return 1e3 * ((t1 - t0) - inside) / len(steps)
