"""Share of its roofline the flash-prefill kernel reaches (%): the least time
of the causal attention the prefills inside the traced stretch needed
(``flops.flash_prefill``) over the kernel's device time in the prefill
programs (``readers.kernel_time``)."""
from chipbench import flops, readers


def read(rec):
    pf = readers.traced(rec, rec["prefills"])
    t = readers.kernel_time(rec, "jit__prefill_one")
    if not pf or not t:
        return None
    least = sum(flops.least_time(flops.flash_prefill(rec["dims"], p[2]), rec["peaks"])
                for p in pf)
    return 100.0 * least / t
