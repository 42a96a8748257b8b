"""Mean ``engine.step`` span, to ``block_until_ready``, over the decode steps
that started in the window (ms)."""
from chipbench import readers


def read(rec):
    steps = readers.window_steps(rec)
    if not steps:
        return None
    return 1e3 * sum(s[1] - s[0] for s in steps) / len(steps)
