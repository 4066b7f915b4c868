"""Share of the traced window in which no operation ran on the device
(1 - busy / window, busy the union of the device's op intervals), in %."""


def read(rec):
    tr = rec["window"].get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
