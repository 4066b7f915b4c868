"""The check that decides `correct`, driven through the rest of a run on the
CPU at a small size (the harness's look for a chip is skipped): a sound
run passes; the control (the reference in the precision below the
configuration's, put in the program's place) and each fault that a
training cell can have, planted underneath the timed path, fail."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cnn_cell():
    """The paper-cnn configuration and chain traffic files with the widths
    and traffic cut to what a test can run. The limits are set for this
    size on the CPU, where the program and the reference both compute in
    f32: sound runs read under 3e-7, the three-pass control 3.6e-5 and
    more, on three seeds."""
    config = harness.load_json(harness.BENCH / "configs" / "paper-cnn.json")
    traffic = harness.load_json(harness.BENCH / "traffic" / "chain.json")
    config = dict(config, overrides={"d_model": 8, "d_ff": 32},
                  conv_widths=[8, 16, 32], fc_widths=[32, 10])
    traffic = dict(traffic, clients=2, samples_per_client=96, batch=16,
                   pool_size=2, e_warmup=3, e_local=5, learning_rate=1e-3)
    return harness.Cell("tiny-cnn.chain", config, traffic, 1,
                        [{"name": "train_samples_per_s", "unit": "samples/s"},
                         {"name": "setup_s", "unit": "s"}], [],
                        {"loss_gap": 5e-6, "change_gap": 5e-6})


def run(cell, seed=2**31 + 7):
    import jax
    return harness.run_cell(cell, seed, 0.2, False, jax.devices()[:1],
                            PEAKS, time.time())


def failed(line):
    return [n for n, c in line["checks"].items() if not c["value"] <= c["limit"]]


def test_sound_run_is_correct():
    line = run(tiny_cnn_cell())
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


def test_control_is_not_correct(monkeypatch):
    """The reference in the precision below the configuration's (three
    bfloat16 passes for f32 at highest) in the program's place."""
    cell = tiny_cnn_cell()
    mod = harness.load_module("drivers", cell.traffic["driver"])

    class Control(mod.Driver):
        def readings(self, control=None):
            return super().readings(cell.config["control"])

    real = harness.load_module
    monkeypatch.setattr(harness, "load_module", lambda kind, name: (
        type("m", (), {"Driver": Control}) if kind == "drivers"
        else real(kind, name)))
    line = run(cell)
    assert not line["correct"], line["checks"]
    assert failed(line)


def _state_unchanged(monkeypatch):
    from repro.api import trainer
    real = trainer.make_optimizer

    def frozen(name, lr, wd=0.0, **kw):
        opt = real(name, lr, wd, **kw)
        return opt._replace(update=lambda p, g, s, step: (p, s))

    monkeypatch.setattr(trainer, "make_optimizer", frozen)


def _half_batch(monkeypatch):
    from repro.api import trainer
    real = trainer.fused_loss_for

    def half(loss_fn):
        base = real(loss_fn)
        return lambda p, b: base(p, {k: v[:v.shape[0] // 2]
                                     for k, v in b.items()})

    monkeypatch.setattr(trainer, "fused_loss_for", half)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    line = run(tiny_cnn_cell())
    assert not line["correct"], line["checks"]
