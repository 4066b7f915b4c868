"""Operation and byte counts the metrics use, against numbers worked by
hand for the two configurations as committed."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import harness  # noqa: E402


def _json(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def test_paper_cnn_train_flops_per_sample():
    cfg = _json("configs", "paper-cnn.json")
    ref = harness.load_module("reference", "cnn")
    # forward: conv1 32*32*64*27*2, conv2 16*16*128*576*2,
    # conv3 8*8*256*1152*2, fc1 4096*256*2, fc2 256*10*2
    conv = [3_538_944, 37_748_736, 37_748_736]
    fwd = sum(conv) + 2_097_152 + 5_120
    assert ref.forward_flops_per_sample(cfg) == fwd == 81_138_688
    # backward: weight gradients of all, input gradients of all but conv1
    assert ref.train_flops_per_sample(cfg, {}) == 3 * fwd - conv[0]


def test_im2col_gemm_step_work_at_batch_64():
    cfg = _json("configs", "paper-cnn.json")
    roof = harness.load_module("metrics", "im2col_gemm_roofline")
    ops, nbytes = roof.step_work(cfg, 64)
    # (M, K, N): (65536, 27, 64), (16384, 576, 128), (4096, 1152, 256);
    # conv1 runs forward + weight gradient, the others one more product
    gemm = [2 * 65536 * 27 * 64, 2 * 16384 * 576 * 128,
            2 * 4096 * 1152 * 256]
    assert ops == 2 * gemm[0] + 3 * gemm[1] + 3 * gemm[2] == 14_948_499_456
    mem = [4 * (65536 * 27 + 27 * 64 + 65536 * 64),
           4 * (16384 * 576 + 576 * 128 + 16384 * 128),
           4 * (4096 * 1152 + 1152 * 256 + 4096 * 256)]
    assert nbytes == 2 * mem[0] + 3 * mem[1] + 3 * mem[2] == 259_765_760


def test_granite_cut_train_flops_per_sequence():
    cfg = _json("configs", "granite-8b.json")
    traffic = _json("traffic", "silo_train.json")
    ref = harness.load_module("reference", "decoder")
    d, f, t = 4096, 14336, 2048
    # per layer: wq, wo 4096x4096; wk, wv 4096x1024; gate, up, down
    layer = 2 * d * d + 2 * d * 1024 + 3 * d * f
    assert layer == 218_103_808
    matrices = cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]
    attn = cfg["num_hidden_layers"] * 3 * 4 * 32 * 128 * t / 2
    assert ref.train_flops_per_sample(cfg, traffic) == pytest.approx(
        t * (6 * matrices + attn))
    # three layers and the head slice: about 8.6 TFLOP per sequence
    assert 8.5e12 < ref.train_flops_per_sample(cfg, traffic) < 8.8e12


def test_mfu_reads_rate_times_flops_over_peak():
    cfg = _json("configs", "paper-cnn.json")
    mfu = harness.load_module("metrics", "mfu.train")
    rec = {"config": cfg, "traffic": {}, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12},
           "window": {"work": 1000, "seconds": 2.0, "trace": {"x": 1}}}
    per = harness.load_module("reference", "cnn").train_flops_per_sample(
        cfg, {})
    assert mfu.read(rec) == pytest.approx(100 * per * 500 / 197e12)
    rec["window"]["trace"] = None
    assert mfu.read(rec) is None
