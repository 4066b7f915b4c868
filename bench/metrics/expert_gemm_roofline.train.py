"""Roofline share of the held experts' grouped GEMM (the megablox kernel,
`kernels/ops.grouped_matmul`), in %: the least time the chip could take
for the kernel's calls in the traced window, max(operations / peak FLOP/s,
bytes / peak bandwidth), over their device time.

Every call (gate, up and down forward, their recompute where the layer is
recomputed, the input and the weight gradients) is 2 x rows x d x f
operations over the rows that reach the held experts
(`reference.expert_gemm_work`); the rows are the mean over the window's
steps and MoE layers that the model's routing counter gives
(`bench/moe_scopes.held_rows`), not the nominal k x held / E share, which
random weights' routing need not meet."""
from bench import harness, moe_scopes


def read(rec):
    sp = moe_scopes.read(rec)
    if sp is None or sp["kernel"] <= 0:
        return None
    rows = moe_scopes.held_rows(rec)
    if rows is None:
        return None
    ref = harness.load_module("reference", rec["config"]["reference"])
    ops, nbytes = ref.expert_gemm_work(rec["config"], rows)
    peaks = rec["peaks"]
    t_ops = sp["kernel_calls"] * ops / peaks["bf16_flops_per_s"]
    t_bytes = sp["kernel_calls"] * nbytes / peaks["hbm_bytes_per_s"]
    harness.say(f"expert_gemm: {sp['kernel_calls']} calls, kernel "
                f"{sp['kernel']:.6f} s, {rows:.1f} rows a call, bound by "
                f"{'bandwidth' if t_bytes > t_ops else 'compute'} "
                f"(ops {t_ops:.6f} s, bytes {t_bytes:.6f} s)")
    return 100.0 * max(t_ops, t_bytes) / sp["kernel"]
