"""The local phase's program is a function of the code and its inputs: no
environment variable changes what the benchmark cells' decoders lower to.

Each case sets one variable that once tuned the traced program to a value
other than its old default, builds the model anew (jax caches a trace by
the function) and lowers `scanned_local`; the text must equal the one
lowered with every such variable unset. Lowering only, nothing compiles.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.api import LocalTrainer
from repro.configs import FedConfig, get_arch
from repro.models import build_model

KNOBS = {"REPRO_SCAN_UNROLL": "2", "REPRO_INNER_UNROLL": "full",
         "REPRO_ATTN_BLOCK": "64", "REPRO_GLA_CHUNK": "16",
         "REPRO_ATTN_SEQ_SHARD": "single", "REPRO_GQA_REPEAT": "1",
         "REPRO_ACT_SHARD": "single", "REPRO_REMAT_SEGMENTS": "2",
         "REPRO_MICROBATCH": "2", "REPRO_POOL_FORM": "exact"}
# The reduced decoders of the two benchmark cells, in their dtype, with
# their low-rank pool.
CELLS = ("granite-8b", "deepseek-v2-lite-16b")
FED = FedConfig(n_clients=2, pool_size=2, e_local=2, e_warmup=2,
                learning_rate=1e-3, optimizer="adam", pool_backend="lowrank",
                pool_rank=8)
T, B, N = 128, 2, 4


def _lowered_text(arch: str) -> str:
    cfg = dataclasses.replace(get_arch(arch).reduced(),
                              param_dtype="bfloat16")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rows = jax.ShapeDtypeStruct((N, T), jnp.int32)
    idx = jax.ShapeDtypeStruct((FED.pool_size, FED.e_local, B), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    tr = LocalTrainer(model.loss_fn, FED)
    return tr.scanned_local.lower(
        shapes, {"tokens": rows, "labels": rows}, idx, scalar,
        scalar).as_text()


@pytest.fixture(scope="module")
def baseline():
    with pytest.MonkeyPatch.context() as mp:
        for name in KNOBS:
            mp.delenv(name, raising=False)
        return {arch: _lowered_text(arch) for arch in CELLS}


@pytest.mark.parametrize("arch", CELLS)
@pytest.mark.parametrize("knob", sorted(KNOBS))
def test_cell_programs_ignore_the_environment(knob, arch, baseline,
                                              monkeypatch):
    for name in KNOBS:
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setenv(knob, KNOBS[knob])
    same = _lowered_text(arch) == baseline[arch]
    assert same, f"{knob}={KNOBS[knob]} changed {arch}'s lowered program"
