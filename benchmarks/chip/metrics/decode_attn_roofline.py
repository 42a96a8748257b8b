"""Share of its roofline the decode-attention kernel reaches (%): the least
time of the work the decode steps inside the traced stretch needed (the
cell's reference's ``decode_work`` for ``decode_attention``: K and V of each
active row up to its length) over the kernel's device time in the decode
program (``readers.kernel_time``)."""
from chipbench import flops, readers

KERNEL = "decode_attention"


def read(rec):
    steps = readers.traced(rec, rec["steps"])
    t = readers.kernel_time(rec, "jit__decode", KERNEL)
    if not steps or not t:
        return None
    work = [rec["ref"].decode_work(rec["dims"], s[2], s[4])["kernels"].get(KERNEL)
            for s in steps]
    if None in work:
        return None
    return 100.0 * sum(flops.least_time(w, rec["peaks"]) for w in work) / t
