"""Share of the traced stretch in which no operation ran on the device (%):
1 - the union of the operations' intervals over the stretch's length."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
