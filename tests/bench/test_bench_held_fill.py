"""The held path's fill (`bench/metrics/held_fill.train.py`) on a tiny
deepseek-v2-lite chain's driver after its set-up on the CPU, read from a
recorded fake of a traced window: sum H / sum C over the window's steps
and MoE layers, H from the routing counter and C from the program's
`held_capacity`; None without `held_capacity`, as a program from before
the row ladder has none, and None without a trace."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"),
                os.path.dirname(os.path.abspath(__file__))]

from bench import harness  # noqa: E402
import test_bench_check_mla_moe as tiny_cell  # noqa: E402


@pytest.fixture(scope="module")
def driver():
    import jax
    cell = tiny_cell.tiny(**tiny_cell.SIZES)
    mod = harness.load_module("drivers", cell.traffic["driver"])
    drv = mod.Driver(cell.config, cell.traffic, 2**33 + 11,
                     jax.devices()[:1], cell.limits, 0.1)
    drv.setup()
    return drv


def _rec(driver, units=2):
    t = driver.traffic
    work = units * t["batch"] * (t["e_warmup"]
                                 + t["pool_size"] * t["e_local"])
    tr = {"events": [], "busy_s": 1.0, "window_s": 1.0}
    return {"traffic": t, "config": driver.config, "driver": driver,
            "peaks": tiny_cell.PEAKS,
            "window": {"units": [1.0] * units, "seconds": 1.0,
                       "work": work, "trace": tr}}


def _read(rec):
    return harness.load_module("metrics", "held_fill.train").read(rec)


def test_fill_is_routed_rows_over_the_rungs_they_took(driver):
    import jax
    from repro.models import moe
    t = driver.traffic
    per = t["e_warmup"] + t["pool_size"] * t["e_local"]
    plan = driver.exp.client_iters[0]
    fn = jax.jit(driver.model.route_counts)
    n = t["batch"] * t["seq_len"]
    # one MoE layer holding 4 of 8 experts, top-3, 512 tokens a step
    assert moe.held_rungs(n, 3, 4, 8) == (1024, 1536)
    rows = cap = 0
    for r in plan.peek_schedule(3 * per)[per:]:
        counts = np.asarray(fn(driver.exp.init_params, {
            k: v[r] for k, v in plan.arrays.items()}))
        assert counts.shape == (1, 4)
        h = int(counts.sum())
        rows += h
        cap += 1024 if h <= 1024 else 1536
    assert _read(_rec(driver)) == pytest.approx(100.0 * rows / cap)
    assert 0 < _read(_rec(driver)) <= 100


def test_none_without_held_capacity_or_trace(driver, monkeypatch):
    from repro.models import moe
    rec = _rec(driver)
    monkeypatch.delattr(moe, "held_capacity")
    assert _read(rec) is None
    monkeypatch.undo()
    rec["window"].pop("trace")
    assert _read(rec) is None
