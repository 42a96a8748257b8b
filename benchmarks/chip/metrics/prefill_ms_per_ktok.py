"""Prefill time per thousand prompt tokens (ms): the ``engine.add_request``
spans that started in the window (the prefill program, the slot write, and
the wait for both), authoritative and speculative, over their prompt tokens."""
from chipbench import readers


def read(rec):
    pf = readers.window_prefills(rec)
    n = sum(p[2] for p in pf)
    if not n:
        return None
    return 1e6 * sum(p[1] - p[0] for p in pf) / n
