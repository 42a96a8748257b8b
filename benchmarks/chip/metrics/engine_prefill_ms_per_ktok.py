"""Prefill time per thousand prompt tokens (ms), as the program times it: the
``engine.prefill`` spans that started in the window (the prefill program to
its first token, and the dispatch of the slot write), authoritative and
speculative, over their ``tokens``."""
from chipbench import program_spans


def read(rec):
    pf = program_spans.window_spans(rec, "engine.prefill")
    n = sum(a["tokens"] for _, _, _, a in pf)
    if not n:
        return None
    return 1e6 * sum(t1 - t0 for _, t0, t1, _ in pf) / n
