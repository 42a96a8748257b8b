"""Model FLOP utilization of prefill (%): the model operations of the
prompts prefilled in the window (the cell's reference's ``prefill_work``)
over their ``engine.add_request`` span time times the chip's bf16 peak."""
from chipbench import readers


def read(rec):
    pf = readers.window_prefills(rec)
    t = sum(p[1] - p[0] for p in pf)
    if not t:
        return None
    work = sum(rec["ref"].prefill_work(rec["dims"], p[2])["model_flops"] for p in pf)
    return 100.0 * work / (t * rec["peaks"]["bf16_flops_per_s"])
