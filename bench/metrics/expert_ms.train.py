"""Device time of the held experts' grouped GEMMs and their SwiGLU (the
program's `moe.experts` scope, inside `step.task`), forward and backward,
in ms per training step: every step of every launch in the traced window
(`bench/moe_scopes.py`)."""
from bench import moe_scopes


def read(rec):
    sp = moe_scopes.read(rec)
    steps = rec["window"]["work"] / rec["traffic"]["batch"]
    if sp is None or steps <= 0:
        return None
    return 1e3 * sp.get(moe_scopes.EXPERTS, 0.0) / steps
