"""Speculative rows as a share of the active rows, over the decode steps
that started in the window (%)."""
from chipbench import readers


def read(rec):
    steps = readers.window_steps(rec)
    active = sum(s[2] for s in steps)
    if not active:
        return None
    return 100.0 * sum(s[3] for s in steps) / active
