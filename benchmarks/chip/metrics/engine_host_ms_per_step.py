"""The engine's own host time per decode step (ms): each ``engine.decode``
span that started in the window less the ``engine.decode.wait`` inside it
(the fetch that waits for the device), on average; what is left is the
dispatch in ``engine.decode.prep`` and the per-slot loop in
``engine.decode.emit``."""
from chipbench import program_spans


def read(rec):
    steps = program_spans.window_spans(rec, "engine.decode")
    if not steps:
        return None
    wait = program_spans.child_seconds(steps, "engine.decode.wait")
    return 1e3 * (sum(t1 - t0 for _, t0, t1, _ in steps) - sum(wait)) / len(steps)
