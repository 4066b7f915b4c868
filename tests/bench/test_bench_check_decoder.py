"""The check that decides `correct` for the granite-8b configuration, driven
through the rest of a run on the CPU at a small size (the harness's look
for a chip is skipped): a sound run of the local phase passes; the control
(the reference with fp8 operands put in the program's place) and each
fault that the cell can have, planted underneath the timed path (a step
that returns its state unchanged, half of the batch left out), fail."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SMALL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=1,
             vocab_size=256, param_dtype="float32")
OVERRIDES = dict(n_layers=1, vocab_size=256, d_model=64, n_heads=4,
                 n_kv_heads=2, d_ff=128, head_dim=16, param_dtype="float32")


def tiny(name, **traffic):
    """The cell's files with the widths cut to what a test can run; the
    traffic's lengths cut as given; the limits are the cell's own."""
    cell = harness.resolve(name)
    config = dict(cell.config, **SMALL,
                  overrides=dict(cell.config["overrides"], **OVERRIDES))
    return cell._replace(config=config, traffic=dict(cell.traffic, **traffic))


def run(cell, seconds, seed=2**31 + 11):
    import jax
    return harness.run_cell(cell, seed, seconds, False, jax.devices()[:1],
                            PEAKS, time.time())


def test_silo_train_sound_run_is_correct():
    cell = tiny("granite-8b.silo_train", seqs_per_client=8, seq_len=256,
                batch=2)
    line = run(cell, 0.2)
    assert line["correct"], line["checks"]


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


def _control(monkeypatch):
    """The reference with fp8 operands in the program's place."""
    mod = harness.load_module("drivers", "train_chain")
    dtype = harness.load_json(harness.BENCH / "configs"
                              / "granite-8b.json")["control"]

    class Control(mod.Driver):
        def readings(self, control=None):
            return super().readings(dtype)

    real = harness.load_module
    monkeypatch.setattr(harness, "load_module", lambda kind, name: (
        type("m", (), {"Driver": Control}) if kind == "drivers"
        else real(kind, name)))


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch, _control],
                         ids=["state_unchanged", "half_batch", "control"])
def test_silo_train_fault_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    cell = tiny("granite-8b.silo_train", seqs_per_client=8, seq_len=256,
                batch=2)
    line = run(cell, 0.2)
    assert not line["correct"], line["checks"]


def test_reference_follows_the_program_with_qkv_biases():
    """The published Granite Code values the cell is to take (q, k and v
    biases, rope_theta 1e7): the reference's loss at the program's weights
    equals the program's, in f32 at a small size."""
    import jax
    from repro.models import build_model
    cell = tiny("granite-8b.silo_train", seqs_per_client=4, seq_len=256,
                batch=2)
    config = dict(cell.config, rope_theta=1e7, overrides=dict(
        cell.config["overrides"], rope_theta=1e7, qkv_bias=True))
    model = build_model(harness.arch_config(config))
    ref = harness.load_module("reference", "decoder")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = ref.init_params(shapes, jax.random.PRNGKey(5))
    assert {"bq", "bk", "bv"} <= set(params["layers"]["attn"])
    batch = {k: v[:2] for k, v in ref.make_data(
        config, cell.traffic, jax.random.PRNGKey(6))[0].items()}
    got = float(model.loss_fn(params, batch))
    want = float(ref.loss(config, params, batch))
    assert abs(got - want) < 1e-4 * abs(want), (got, want)
