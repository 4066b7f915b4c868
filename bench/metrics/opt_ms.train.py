"""Device time of the optimizer update (the program's `step.opt` scope),
in ms per training step: every step of every launch in the traced window,
warmup included (`bench/scopes.py`). XLA may fuse the update into ops
rooted in the backward pass, which then count there."""
from bench import scopes


def read(rec):
    return scopes.per_unit(rec, ["step.opt"],
                           rec["window"]["work"] / rec["traffic"]["batch"])
