"""Model FLOP utilization of decode (%): the model operations of the active
rows at their context lengths (the cell's reference's ``decode_work``), over
the decode steps that started in the window, over their span time times the
chip's bf16 peak."""
from chipbench import readers


def read(rec):
    steps = readers.window_steps(rec)
    t = sum(s[1] - s[0] for s in steps)
    if not t:
        return None
    work = sum(rec["ref"].decode_work(rec["dims"], s[2], s[4])["model_flops"] for s in steps)
    return 100.0 * work / (t * rec["peaks"]["bf16_flops_per_s"])
