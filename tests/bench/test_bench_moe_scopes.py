"""A MoE layer's device time by its names (`bench/moe_scopes.py`) and the
three metrics that read it, on a tiny deepseek-v2-lite chain's driver
after its set-up on the CPU: the local phase's programs carry the four
MoE names; events made from their instructions (and a grouped-GEMM
kernel call, which the CPU's jnp twin does not make) are charged to the
right names; the routing counter's rows are those of the window's steps;
a program without the names reads None."""
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"),
                os.path.dirname(os.path.abspath(__file__))]

from bench import harness, moe_scopes, scopes, trace  # noqa: E402
import test_bench_check_mla_moe as tiny_cell  # noqa: E402

MOE = ("moe.route", "moe.dispatch", "moe.experts", "moe.shared")
KERNEL = ('%custom-call.9 = bf16[64,32]{1,0} custom-call(bf16[64,64]{1,0} '
          '%p.1, bf16[4,64,32]{2,1,0} %p.2), custom_call_target='
          '"tpu_custom_call", metadata={op_name="jit(core)/while/body/'
          'step.task/moe.experts/gmm"}')


@pytest.fixture(scope="module")
def driver():
    import jax
    cell = tiny_cell.tiny(**tiny_cell.SIZES)
    mod = harness.load_module("drivers", cell.traffic["driver"])
    drv = mod.Driver(cell.config, cell.traffic, 2**33 + 7, jax.devices()[:1],
                     cell.limits, 0.1)
    drv.setup()
    return drv


@pytest.fixture(scope="module")
def texts(driver):
    return scopes.programs(driver) + [KERNEL]


def _instr(texts, scope):
    """The first instruction line of `scope` in the programs."""
    tab = scopes.table(texts, MOE)
    for line in "\n".join(texts).splitlines():
        k = scopes.key_of(line)
        if k is not None and tab.get(k) == scope and k[2] != "custom-call":
            return line.strip().removeprefix("ROOT ").split(", metadata=")[0]
    raise AssertionError(scope)


def _rec(driver, events, units=2):
    t = driver.traffic
    work = units * t["batch"] * (t["e_warmup"]
                                 + t["pool_size"] * t["e_local"])
    tr = {"events": events, "busy_s": 1.0, "window_s": 1.0}
    return {"traffic": t, "config": driver.config, "driver": driver,
            "peaks": tiny_cell.PEAKS,
            "window": {"units": [1.0] * units, "seconds": 1.0,
                       "work": work, "trace": tr}}


def test_programs_carry_every_moe_name(texts):
    tab = scopes.table(texts, MOE)
    assert set(tab.values()) - {None} == set(MOE)


def test_split_kernel_and_metrics(driver, texts, monkeypatch):
    monkeypatch.setattr(scopes, "programs", lambda drv: texts)
    kern = KERNEL.split(", metadata=")[0]
    events = [[_instr(texts, "moe.route"), 0, 1000],
              [_instr(texts, "moe.dispatch"), 2000, 3000],
              [_instr(texts, "moe.experts"), 6000, 500],
              [kern, 7000, 4000], [kern, 12000, 4000],
              [_instr(texts, "moe.shared"), 20000, 250]]
    rec = _rec(driver, events)
    sp = moe_scopes.read(rec)
    assert sp["moe.route"] == pytest.approx(1e-6)
    assert sp["moe.dispatch"] == pytest.approx(3e-6)
    assert sp["moe.experts"] == pytest.approx(8.5e-6)
    assert sp["moe.shared"] == pytest.approx(0.25e-6)
    assert sp["kernel"] == pytest.approx(8e-6) and sp["kernel_calls"] == 2
    steps = rec["window"]["work"] / rec["traffic"]["batch"]
    read = lambda n: harness.load_module("metrics", n).read(rec)  # noqa
    assert read("expert_ms.train") == pytest.approx(1e3 * 8.5e-6 / steps)
    assert read("route_ms.train") == pytest.approx(1e3 * 4e-6 / steps)
    rows = moe_scopes.held_rows(rec)
    ops, nbytes = harness.load_module("reference", "mla_moe"
                                      ).expert_gemm_work(driver.config, rows)
    want = 100 * max(2 * ops / tiny_cell.PEAKS["bf16_flops_per_s"],
                     2 * nbytes / tiny_cell.PEAKS["hbm_bytes_per_s"]) / 8e-6
    assert read("expert_gemm_roofline.train") == pytest.approx(want)


def test_held_rows_count_the_window_steps(driver):
    import jax
    rec = _rec(driver, [], units=2)
    t = driver.traffic
    per = t["e_warmup"] + t["pool_size"] * t["e_local"]
    plan = driver.exp.client_iters[0]
    rows = plan.peek_schedule(3 * per)[per:]
    fn = jax.jit(driver.model.route_counts)
    counts = np.stack([np.asarray(fn(driver.exp.init_params, {
        k: v[r] for k, v in plan.arrays.items()})) for r in rows])
    assert counts.shape[1:] == (1, 4)        # one MoE layer, 4 held
    assert moe_scopes.held_rows(rec) == pytest.approx(counts.sum()
                                                      / (2 * per))
    assert 0 < counts.sum() <= counts.size / 4 * t["batch"] * \
        t["seq_len"] * 3


def test_left_out_without_names_or_trace(driver, texts, monkeypatch):
    bare = [line.split(", metadata=")[0] for line in
            "\n".join(texts).splitlines()]
    monkeypatch.setattr(scopes, "programs", lambda drv: ["\n".join(bare)])
    rec = _rec(driver, [[_instr(texts, "moe.route"), 0, 10]])
    for name in ("expert_ms.train", "route_ms.train",
                 "expert_gemm_roofline.train"):
        assert harness.load_module("metrics", name).read(rec) is None
    rec["window"].pop("trace")
    assert moe_scopes.read(rec) is None and moe_scopes.held_rows(rec) is None
    assert moe_scopes.read({"window": {"trace": {}}, "driver":
                            types.SimpleNamespace(exp=None)}) is None
