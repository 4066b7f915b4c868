"""Model FLOP/s utilization of training, in % of the chips' bf16 peak: the
operations the forward and backward passes require per sample (the
configuration's reference counts them from its shapes; recomputation is
not counted) times the samples per second of the traced window."""
from bench import harness


def read(rec):
    tr = rec["window"].get("trace")
    if not tr:
        return None
    ref = harness.load_module("reference", rec["config"]["reference"])
    flops = ref.train_flops_per_sample(rec["config"], rec["traffic"])
    rate = rec["window"]["work"] / rec["window"]["seconds"]
    return 100.0 * flops * rate / (rec["chips"]
                                   * rec["peaks"]["bf16_flops_per_s"])
