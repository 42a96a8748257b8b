"""Share of its roofline the decode-attention kernel reaches (%): the least
time of the work the decode steps inside the traced stretch needed
(``flops.decode_attn``: K and V of each active row up to its length) over
the kernel's device time in the decode program (``readers.kernel_time``)."""
from chipbench import flops, readers


def read(rec):
    steps = readers.traced(rec, rec["steps"])
    t = readers.kernel_time(rec, "jit__decode")
    if not steps or not t:
        return None
    least = sum(flops.least_time(flops.decode_attn(rec["dims"], s[2], s[4]), rec["peaks"])
                for s in steps)
    return 100.0 * least / t
