"""Share of its roofline the flash-prefill kernel reaches (%): the least time
of the causal attention the prefills inside the traced stretch needed (the
cell's reference's ``prefill_work`` for ``flash_attention``) over the
kernel's device time in the prefill programs (``readers.kernel_time``)."""
from chipbench import flops, readers

KERNEL = "flash_attention"


def read(rec):
    pf = readers.traced(rec, rec["prefills"])
    t = readers.kernel_time(rec, "jit__prefill_one", KERNEL)
    if not pf or not t:
        return None
    work = [rec["ref"].prefill_work(rec["dims"], p[2])["kernels"].get(KERNEL) for p in pf]
    if None in work:
        return None
    return 100.0 * sum(flops.least_time(w, rec["peaks"]) for w in work) / t
