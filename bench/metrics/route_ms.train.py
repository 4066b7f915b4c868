"""Device time of a MoE layer's routing (the program's `moe.route` scope:
router, top-k, balance loss) and of the permutation of the assignments to
and from the held experts (`moe.dispatch`), forward and backward, in ms
per training step over the traced window (`bench/moe_scopes.py`)."""
from bench import moe_scopes


def read(rec):
    sp = moe_scopes.read(rec)
    steps = rec["window"]["work"] / rec["traffic"]["batch"]
    if sp is None or steps <= 0:
        return None
    return 1e3 * (sp.get("moe.route", 0.0)
                  + sp.get("moe.dispatch", 0.0)) / steps
