"""Device time of the task loss, its batch gather and their backward
(the program's `step.task` scope), in ms per training step: every step of
every launch in the traced window, warmup included (`bench/scopes.py`)."""
from bench import scopes


def read(rec):
    return scopes.per_unit(rec, ["step.task"],
                           rec["window"]["work"] / rec["traffic"]["batch"])
