"""Device time of the d1/d2 regularizer, its log-scale calibration and
their backward (the program's `step.reg` scope), in ms per regularized
step: clients x pool_size x e_local of each launch in the traced window
(`bench/scopes.py`)."""
from bench import scopes


def read(rec):
    t = rec["traffic"]
    steps = (len(rec["window"]["units"]) * t["clients"] * t["pool_size"]
             * t["e_local"])
    return scopes.per_unit(rec, ["step.reg"], steps)
