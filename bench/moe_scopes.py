"""A MoE layer's device time by the names the program gives its work
(`repro.obs.MOE_SCOPES`), the held experts' grouped-GEMM kernel time, and
the rows that the window's steps routed to the held experts.

The MoE scopes lie inside `step.task`, so `bench/scopes.py`'s split, which
charges an op to the outermost of the step's names, never sees them. Here
each event is mapped through the same programs' HLO (`scopes.programs`)
to the MoE scope in its op's `op_name` path, and its own time charged
there. The kernel's events are the `custom-call`s in `moe.experts` (the
megablox grouped GEMM, forward and backward; a TPU trace names no kernel).

Rows: the model's forward-only `route_counts` over the rows of every
step the window's launches took (each launch starts from the set-up's
weights, so the counts are taken at those), as the layers' grouped GEMMs
take them.

Against a program without these names or without `route_counts`, as
before them, `read` and `held_rows` return None and the metrics that read
them are left out.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from bench import scopes, trace

EXPERTS = "moe.experts"
_MEMO: Dict[int, tuple] = {}


def _driver(rec):
    driver = rec.get("driver")
    if not rec["window"].get("trace") or driver is None or \
            getattr(driver, "exp", None) is None:
        return None
    return driver


def _memo(rec, key, fn):
    tr = rec["window"]["trace"]
    memo = _MEMO.get(id(tr))
    if memo is None or memo[0] is not tr:
        _MEMO.clear()
        memo = _MEMO[id(tr)] = (tr, {})
    if key not in memo[1]:
        memo[1][key] = fn()
    return memo[1][key]


def read(rec) -> Optional[Dict[str, float]]:
    """Seconds of each MoE scope's own device time in the traced window on
    device 0, and under "kernel" the grouped-GEMM events' seconds and
    under "kernel_calls" their count; None where there is no trace or the
    program names no MoE scope."""
    driver = _driver(rec)
    if driver is None:
        return None
    try:
        from repro.obs import MOE_SCOPES
    except ImportError:         # a program from before the names
        return None

    def compute():
        tab = scopes.table(scopes.programs(driver), MOE_SCOPES)
        if not any(tab.values()):
            return None
        events = rec["window"]["trace"]["events"]
        out = {k: v / 1e9 for k, v in trace.self_times(
            [[tab.get(scopes.key_of(n)) or scopes.UNSCOPED, s, d]
             for n, s, d in events]).items()}
        kernel = [d for n, _, d in events
                  if tab.get(scopes.key_of(n)) == EXPERTS
                  and scopes.key_of(n)[2] == "custom-call"]
        out["kernel"] = sum(kernel) / 1e9
        out["kernel_calls"] = len(kernel)
        return out
    return _memo(rec, "split", compute)


def held_rows(rec) -> Optional[float]:
    """Mean rows routed to the held experts per step and MoE layer, over
    the steps of the window's launches; None without `route_counts`."""
    driver = _driver(rec)
    route = getattr(getattr(driver, "model", None), "route_counts", None)
    if route is None:
        return None

    def compute():
        import jax
        t = rec["traffic"]
        exp = driver.exp
        fn = jax.jit(route)
        units = len(rec["window"]["units"])
        total, steps = 0, 0
        for i, plan in enumerate(exp.client_iters):
            per = t["pool_size"] * t["e_local"] + (t["e_warmup"] if i == 0
                                                   else 0)
            rows = plan.peek_schedule(per * (1 + units))[per:]
            for r in rows:
                counts = fn(exp.init_params,
                            {k: v[r] for k, v in plan.arrays.items()})
                total += int(np.asarray(counts).sum())
                steps += counts.shape[0]
        return total / steps if steps else None
    return _memo(rec, "rows", compute)
