"""Device time by the program's scope names (`bench/scopes.py`) and the
per-layer metrics that read it, on a stretch of a trace recorded on a TPU
v5e (granite-8b.silo_train: the end of a client visit's pool and the next
launch's warmup, with the idle gap between), checked against a
brute-force reading of the same events; and the programs' HLO taken from
a driver after its set-up, on the CPU at a small size."""
import json
import math
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, scopes, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "granite_scopes_slice.json")
SCOPES = ("step.task", "step.reg", "step.opt", "pool.create",
          "pool.average", "pool.append")
METRICS = ("task_ms.train", "reg_ms.train", "opt_ms.train",
           "pool_ms.train", "visit_gap_ms.train")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tab(recorded):
    return scopes.table(["\n".join(recorded["hlo"])], SCOPES)


def _events(recorded):
    return recorded["devices"]["/device:TPU:0"]


def _owners_bruteforce(events, labels, step=100):
    """ns by label on a 100 ns grid: a point belongs to the innermost event
    covering it (the latest to start; of equal starts, the shortest)."""
    t0 = min(s for _, s, _ in events)
    t1 = max(s + d for _, s, d in events)
    ev = sorted(zip(labels, events), key=lambda x: (x[1][1], -x[1][2]))
    out = {}
    for p in range(int(t0), int(t1), step):
        own = None
        for lab, (_, s, d) in ev:
            if s > p:
                break
            if p < s + d:
                own = lab
        if own is not None:
            out[own] = out.get(own, 0) + step
    return out


def test_scope_of_takes_the_outermost_name():
    of = lambda p: scopes.scope_of(p, SCOPES)  # noqa: E731
    assert of("jit(core)/while/body/transpose(jvp(step.task))/dot_general"
              ) == "step.task"
    assert of("jit(core)/while/body/step.reg/jvp(step.task)/add"
              ) == "step.reg"
    assert of("jit(core)/pool.append/step.opt/mul") == "pool.append"
    assert of("jit(core)/while/body/step.taskx/mul") is None
    assert of("jit(core)/while/body/add") is None


def test_event_and_hlo_line_give_one_key():
    ev = ("%fusion.7 = (f32[2]{0}, bf16[4,8]{1,0:T(8,128)(2,1)}) "
          "fusion(f32[2]{0} %p.1, f32[2]{0} %p.2), kind=kLoop, "
          "calls=%fused_computation.7")
    line = ("  ROOT %fusion.7 = (f32[2]{0}, bf16[4,8]{1,0:T(8,128)(2,1)}) "
            "fusion(%p.1, %p.2), kind=kLoop, calls=%fused_computation.7, "
            'metadata={op_name="jit(f)/step.opt/mul"}')
    assert scopes.key_of(ev) == scopes.key_of(line) == (
        "fusion.7", "(f32[2]{0}, bf16[4,8]{1,0:T(8,128)(2,1)})", "fusion")
    assert scopes.table([line], SCOPES) == {scopes.key_of(ev): "step.opt"}


def test_recorded_events_are_found_in_the_programs(recorded, tab):
    """All but the small helper programs between the local phase's (the
    schedule's upload converts its rows) are found, by time."""
    ev = _events(recorded)
    keys = [scopes.key_of(name) for name, _, _ in ev]
    assert all(k is not None for k in keys)
    lost = sum(d for k, (_, _, d) in zip(keys, ev) if k not in tab)
    assert lost < 1e-3 * sum(d for _, _, d in ev)


def test_split_matches_bruteforce(recorded, tab):
    ev = _events(recorded)
    sp = scopes.split(ev, tab)
    labels = [tab.get(scopes.key_of(n)) or scopes.UNSCOPED for n, _, _ in ev]
    brute = _owners_bruteforce(ev, labels)
    edges = len(ev)
    assert set(sp) == set(brute)
    for k, v in brute.items():
        assert sp[k] == pytest.approx(v / 1e9, abs=2 * 100e-9 * edges)
    # the stretch holds the regularized step's end, the pool and a warmup
    assert {"step.task", "step.reg", "step.opt", "pool.append",
            "pool.average", scopes.UNSCOPED} <= set(sp)


def test_scoped_plus_unscoped_is_busy(recorded, tab):
    ev = _events(recorded)
    sp = scopes.split(ev, tab)
    busy = sum(e - s for s, e in trace.merge([(s, s + d)
                                              for _, s, d in ev]))
    assert sum(sp.values()) == pytest.approx(busy / 1e9, rel=1e-9)
    assert all(v >= 0 for v in sp.values())


def _rec(recorded, driver, with_trace=True):
    traffic = harness.load_json(harness.BENCH / "traffic" /
                                "silo_train.json")
    window = {"units": [1.0], "seconds": 1.0,
              "work": traffic["batch"] * (traffic["e_warmup"]
                                          + traffic["pool_size"]
                                          * traffic["e_local"])}
    if with_trace:
        window["trace"] = trace.reduce(recorded)
    return {"traffic": traffic, "window": window, "driver": driver}


@pytest.fixture
def driver(recorded, monkeypatch):
    """A driver whose programs are the recorded stretch's HLO lines."""
    monkeypatch.setattr(scopes, "programs",
                        lambda drv: ["\n".join(recorded["hlo"])])
    return types.SimpleNamespace(exp=object())


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_the_recorded_stretch(name, recorded, tab, driver):
    rec = _rec(recorded, driver)
    value = harness.load_module("metrics", name).read(rec)
    assert math.isfinite(value) and value > 0
    sp = scopes.split(rec["window"]["trace"]["events"], tab)
    t = rec["traffic"]
    steps = rec["window"]["work"] / t["batch"]
    want = {
        "task_ms.train": 1e3 * sp["step.task"] / steps,
        "reg_ms.train": 1e3 * sp["step.reg"]
        / (t["clients"] * t["pool_size"] * t["e_local"]),
        "opt_ms.train": 1e3 * sp["step.opt"] / steps,
        "pool_ms.train": 1e3 * (sp["pool.average"] + sp["pool.append"]
                                + sp.get("pool.create", 0.0))
        / (t["clients"] * t["pool_size"]),
        "visit_gap_ms.train": 1e3 * (rec["window"]["trace"]["window_s"]
                                     - rec["window"]["trace"]["busy_s"])
        / t["clients"],
    }[name]
    assert value == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", METRICS)
def test_metric_is_left_out_without_a_trace(name, recorded, driver):
    rec = _rec(recorded, driver, with_trace=False)
    assert harness.load_module("metrics", name).read(rec) is None


def test_scope_metrics_left_out_where_the_program_names_nothing(
        recorded, monkeypatch):
    """A program without scopes (as before they existed) reads None."""
    bare = [line.split(", metadata=")[0] for line in recorded["hlo"]]
    monkeypatch.setattr(scopes, "programs", lambda drv: ["\n".join(bare)])
    rec = _rec(recorded, types.SimpleNamespace(exp=object()))
    for name in METRICS[:4]:
        assert harness.load_module("metrics", name).read(rec) is None


def test_programs_of_a_driver_after_set_up_carry_every_scope():
    """The local phase's programs, lowered again from a tiny chain's
    driver after set-up, hold all six names and compile nothing new."""
    import jax
    config = harness.load_json(harness.BENCH / "configs" / "paper-cnn.json")
    traffic = harness.load_json(harness.BENCH / "traffic" / "chain.json")
    config = dict(config, overrides={"d_model": 8, "d_ff": 32},
                  conv_widths=[8, 16, 32], fc_widths=[32, 10])
    traffic = dict(traffic, clients=2, samples_per_client=32, batch=8,
                   pool_size=2, e_warmup=2, e_local=2)
    mod = harness.load_module("drivers", traffic["driver"])
    drv = mod.Driver(config, traffic, 2**33 + 5, jax.devices()[:1], {}, 0.1)
    harness.use_precision(config)
    try:
        drv.setup()
        with harness.compile_clock() as clock:
            texts = scopes.programs(drv)
    finally:
        harness.use_precision({})
    assert len(texts) == 2 and clock["compiles"] == 0
    tab = scopes.table(texts, SCOPES)
    assert set(tab.values()) - {None} == set(SCOPES)
