"""Speculative slots promoted, as a share of those admitted (%), from the
speculator's own counters over the window and its drain."""


def read(rec):
    if not rec["spec_admitted"]:
        return None
    return 100.0 * rec["spec_promoted"] / rec["spec_admitted"]
