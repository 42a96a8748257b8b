"""Speculative rows thrown away, as a share of the active rows (%): the rows
of the ``engine.slot_release`` counts that arrived in the window with the
outcome ``preempt`` or ``done``, over the ``active`` rows of the
``engine.decode`` spans that started in it."""
from chipbench import program_spans

WASTED = ("preempt", "done")


def read(rec):
    active = sum(a["active"] for _, _, _, a in program_spans.window_spans(rec, "engine.decode"))
    if not active:
        return None
    wasted = sum(v for _, _, v, a in program_spans.window_counts(rec, "engine.slot_release")
                 if a["outcome"] in WASTED)
    return 100.0 * wasted / active
