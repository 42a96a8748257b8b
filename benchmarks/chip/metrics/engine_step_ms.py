"""Mean ``engine.decode`` span, the program's own timing of
``ServingEngine.step`` to its fetch of the next tokens, over the decode
steps that started in the window (ms)."""
from chipbench import program_spans


def read(rec):
    steps = program_spans.window_spans(rec, "engine.decode")
    if not steps:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1, _ in steps) / len(steps)
