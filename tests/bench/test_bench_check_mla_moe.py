"""The check that decides `correct` for the deepseek-v2-lite configuration,
driven through the rest of a run on the CPU at a small size (the
harness's look for a chip is skipped): a sound run of the local phase
passes; the control (the reference with fp8 operands put in the
program's place) and each fault that the cell can have, planted
underneath the timed path (a step that returns its state unchanged, half
of the batch left out), fail. The small size keeps the cell's kinds of
layer: a leading dense layer, then a MoE layer holding 4 of 8 experts,
top-3, 2 shared experts, MLA with the published YaRN scaling."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

CELL = "deepseek-v2-lite.silo_train"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SMALL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=16,
             v_head_dim=16, moe_intermediate_size=32, n_routed_experts=4,
             router_outputs=8, num_experts_per_tok=3, num_hidden_layers=2,
             vocab_size=256, param_dtype="float32")


def overrides():
    from repro.configs import MLAConfig, MoEConfig
    return dict(n_layers=2, vocab_size=256, d_model=64, n_heads=4,
                n_kv_heads=4, dense_d_ff=128, experts_held=4,
                param_dtype="float32",
                mla=MLAConfig(kv_lora_rank=32, qk_rope_dim=16, qk_nope_dim=16,
                              v_head_dim=16),
                moe=MoEConfig(n_experts=8, top_k=3, d_ff_expert=32,
                              n_shared_experts=2))


def tiny(**traffic):
    """The cell's files with the widths cut to what a test can run; the
    traffic's lengths cut as given; the limits are the cell's own."""
    cell = harness.resolve(CELL)
    config = dict(cell.config, **SMALL,
                  overrides=dict(cell.config["overrides"], **overrides()))
    return cell._replace(config=config, traffic=dict(cell.traffic, **traffic))


def run(cell, seconds, seed=2**31 + 23):
    import jax
    return harness.run_cell(cell, seed, seconds, False, jax.devices()[:1],
                            PEAKS, time.time())


SIZES = dict(seqs_per_client=8, seq_len=256, batch=2)


def test_silo_train_sound_run_is_correct():
    line = run(tiny(**SIZES), 0.2)
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
                              / "deepseek-v2-lite.json")["control"]

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
    line = run(tiny(**SIZES), 0.2)
    assert not line["correct"], line["checks"]
