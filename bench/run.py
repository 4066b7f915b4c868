#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`<cell>` is a `workloads` name in BENCHMARK.json. The run builds the cell's
weights and data from `--seed`, warms up every shape it uses (set-up),
measures for `--seconds`, checks what the timed path produced against the
plain reference, and prints one JSON line last. `--trace 1` reports the
cell's per-layer metrics from a profiler trace instead of its end-to-end
metrics. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.time()   # set-up is timed from here, before JAX loads

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
