"""Model FLOP utilization of prefill (%): the model operations of the
prompts prefilled in the window (``flops.model_prefill``) over their
``engine.add_request`` span time times the chip's bf16 peak."""
from chipbench import flops, readers


def read(rec):
    pf = readers.window_prefills(rec)
    t = sum(p[1] - p[0] for p in pf)
    if not t:
        return None
    work = sum(flops.model_prefill(rec["dims"], p[2]) for p in pf)
    return 100.0 * work / (t * rec["peaks"]["bf16_flops_per_s"])
