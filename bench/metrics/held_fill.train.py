"""Fill of the MoE layers' held path, in %: the rows routed to the held
experts over the rows the held path runs at, sum H / sum C over the steps
of the window's launches and the MoE layers. H is the model's routing
counter (`route_counts`, as `bench/moe_scopes.held_rows` takes it, here
per step and layer); C is the program's own row extent for H
(`repro.models.moe.held_capacity`). A program without `held_capacity`,
which runs every layer at all N*k assignments, reads None."""
import numpy as np

from bench import harness, moe_scopes


def read(rec):
    try:
        from repro.models.moe import held_capacity
    except ImportError:         # a program from before the row ladder
        return None
    driver = moe_scopes._driver(rec)
    route = getattr(getattr(driver, "model", None), "route_counts", None)
    if route is None:
        return None
    import jax
    t, exp = rec["traffic"], driver.exp
    moe = harness.arch_config(rec["config"]).moe
    fn = jax.jit(route)
    units = len(rec["window"]["units"])
    rows = cap = 0
    for i, plan in enumerate(exp.client_iters):
        per = t["pool_size"] * t["e_local"] + (t["e_warmup"] if i == 0
                                               else 0)
        for r in plan.peek_schedule(per * (1 + units))[per:]:
            batch = {k: v[r] for k, v in plan.arrays.items()}
            counts = np.asarray(fn(exp.init_params, batch))
            n = batch["tokens"].size
            for h in counts.sum(-1).tolist():
                rows += h
                cap += held_capacity(n, moe.top_k, counts.shape[-1],
                                     moe.n_experts, h)
    return 100.0 * rows / cap if cap else None
