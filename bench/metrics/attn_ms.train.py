"""Device time of the attention of `models/layers.flash_attention` (the
program's `attn.kernel` and `attn.jnp` scopes, inside `step.task`):
forward, its rerun under the layer checkpoint, and backward, in ms per
training step, over every step of every launch in the traced window.

The attention scopes lie inside `step.task`, so `bench/scopes.py`'s
split, which charges an op to the outermost of the step's names, never
sees them. As with the MoE scopes (`bench/moe_scopes.py`), each event is
mapped through the local phase's programs (`scopes.programs`) to the
attention scope in its op's `op_name` path, and its own time charged
there.

Against a program that names neither scope, as before them, `read`
returns None and the metric is left out.
"""
from bench import scopes, trace


def read(rec):
    tr = rec["window"].get("trace")
    driver = rec.get("driver")
    if not tr or driver is None or getattr(driver, "exp", None) is None:
        return None
    try:
        from repro.obs import ATTN_SCOPES
    except ImportError:         # a program from before the names
        return None
    tab = scopes.table(scopes.programs(driver), ATTN_SCOPES)
    if not any(tab.values()):
        return None
    own = trace.self_times([[tab.get(scopes.key_of(n)) or scopes.UNSCOPED,
                             s, d] for n, s, d in tr["events"]])
    steps = rec["window"]["work"] / rec["traffic"]["batch"]
    return 1e3 * sum(own.get(n, 0) for n in ATTN_SCOPES) / 1e9 / steps
