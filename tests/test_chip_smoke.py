"""`chip_smoke.py` on the CPU: its phase functions at tiny sizes, and its
refusal to run without a TPU.

The phases run here through the same entry points as on the chip (the
device check belongs to `main`, so calling a phase directly is how the test
steers past it). What the chip adds — real kernels, real widths, real
memory — is the script's own job.
"""
import dataclasses
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

from repro.configs import get_arch  # noqa: E402

TINY_LLAMA = dataclasses.replace(
    get_arch("llama3.2-1b").reduced(), n_layers=2, d_model=64, n_heads=2,
    n_kv_heads=2, head_dim=32, d_ff=128, vocab_size=256,
    param_dtype="bfloat16")
SEQ, BATCH, N_SEQS = 16, 4, 32


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "cpu" in str(e.value.code)
    assert '"ok": true' not in capsys.readouterr().out


def test_phase_cnn_tiny():
    out = chip_smoke.phase_cnn(n_clients=2, samples_per_client=32, batch=16,
                               pool_size=2, e_local=2, e_warmup=1,
                               n_queries=8, sizes=(1, 3))
    assert out["loss_rel"] <= chip_smoke.CNN_RTOL
    assert out["grad_rel"] <= chip_smoke.CNN_RTOL


@pytest.mark.parametrize("limit,want", [(200, 16), (60, 8), (80, 10),
                                         (14.9, 0)])
def test_reckon_depth_picks_largest_fitting_depth(limit, want):
    """Bytes linear in depth (10 + 5·n), plus a 20-byte step from 9
    layers up that the line through 16 and 8 layers cannot see: at a
    limit of 80 the line picks 12 (90 bytes) and the compiles step down
    to 10; at 60 it picks 9 (75) and steps down to 8. A limit below one
    layer's need fails."""
    def need(n):
        return 10 + 5 * n + (20 if n >= 9 else 0)

    if want == 0:
        with pytest.raises(RuntimeError, match="no depth fits"):
            chip_smoke.reckon_depth(16, need, limit)
        return
    depth, seen = chip_smoke.reckon_depth(16, need, limit)
    assert depth == want and seen[depth] <= limit
    assert depth == 16 or need(depth + 1) > limit


def test_phase_llama_tiny(capsys):
    """Train one low-rank-pool client, serve the pool in factor form and
    compare with the member-by-member reference; the memory reckoning
    compiles the full-depth local phase and finds no cut needed."""
    out = chip_smoke.phase_llama(cfg=TINY_LLAMA, hbm_limit=1e12,
                                 seq_len=SEQ, batch=BATCH, n_seqs=N_SEQS,
                                 pool_size=2, e_local=2, e_warmup=1, rank=4,
                                 n_queries=8, sizes=(1, 5))
    assert out["n_layers"] == TINY_LLAMA.n_layers
    assert "depth_cut=none" in capsys.readouterr().out
    assert out["err"] <= chip_smoke.LLAMA_RTOL * out["scale"]
    assert out["err"] < out["sep"]


def test_phase_fleet_on_one_device():
    """The fleet phase's mesh, placement and comparison logic on the
    single CPU device (the chip run takes four)."""
    out = chip_smoke.phase_fleet(n_devices=1, rounds=1, e_local=2)
    assert out["devices"] == 1
    assert out["params_rel"] <= chip_smoke.FLEET_RTOL
