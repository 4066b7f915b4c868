"""Roofline share of the im2col GEMM kernel (`kernels/local_step.
matmul_blocked`), in %: the least time the chip could take for the GEMMs
the convolutions need, max(operations / peak FLOP/s, bytes / peak
bandwidth), over the kernel's device time in the trace.

Per training step and conv (M = batch * H * W, K = 9 * C_in, N = C_out):
the forward product, the weight gradient, and the input gradient for all
but the first conv, each 2*M*K*N operations and 4*(M*K + K*N + M*N) bytes
of f32 operands and result, unpadded. The kernel's events are the
`tpu_custom_call`s whose two operands and result are rank-2 f32 arrays
(the kernel carries no name of its own in the trace).
"""
import re

from bench import harness

_SHAPE = r"f32\[\d+,\d+\]\{[^}]*\}"
_GEMM = re.compile(rf"= {_SHAPE} custom-call\({_SHAPE} %[^,]+, {_SHAPE} %[^)]+\)"
                   r".*custom_call_target=\"tpu_custom_call\"")


def is_gemm(hlo: str) -> bool:
    return bool(_GEMM.search(hlo))


def step_work(config, batch: int):
    ref = harness.load_module("reference", config["reference"])
    ops = nbytes = 0
    for i, (m, k, n) in enumerate(ref.conv_gemms(config, batch)):
        calls = 2 if i == 0 else 3
        ops += calls * 2 * m * k * n
        nbytes += calls * 4 * (m * k + k * n + m * n)
    return ops, nbytes


def read(rec):
    tr = rec["window"].get("trace")
    if not tr:
        return None
    kernel_ns = sum(d for name, _, d in tr["events"] if is_gemm(name))
    if kernel_ns <= 0:
        return None
    batch = rec["traffic"]["batch"]
    steps = rec["window"]["work"] / batch
    ops, nbytes = step_work(rec["config"], batch)
    peaks = rec["peaks"]
    t_ops = steps * ops / peaks["bf16_flops_per_s"]
    t_bytes = steps * nbytes / peaks["hbm_bytes_per_s"]
    harness.say(f"im2col_gemm: kernel {kernel_ns / 1e9:.6f} s, bound by "
                f"{'bandwidth' if t_bytes > t_ops else 'compute'} "
                f"(ops {t_ops:.6f} s, bytes {t_bytes:.6f} s)")
    return 100.0 * max(t_ops, t_bytes) / (kernel_ns / 1e9)
