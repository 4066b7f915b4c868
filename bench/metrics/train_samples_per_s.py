"""Training samples per second: every row of every step's batch in the
window's units, over the window's length on the host clock."""


def read(rec):
    w = rec["window"]
    return w["work"] / w["seconds"]
