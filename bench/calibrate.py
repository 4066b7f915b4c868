#!/usr/bin/env python3
"""Readings that a cell's check limits are set from (not run by the
benchmark's own runs).

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--seconds S] [--control-seeds 3] [--controls half_batch ...]

For each seed, in one process: the cell's set-up, a window of `--seconds`
at the cell's own load (0: none; a training cell's check reads its
set-up's first launch), then the numbers its check compares, program
against the plain reference: the lower readings. For the first
`--control-seeds` seeds it also reads each control (by default the
configuration's `control`: the reference in the precision below the one
the configuration states; "half_batch": the reference taking each step's
loss over half its batch) put in the program's place: the upper
readings. Prints one JSON line per reading and
the largest and smallest of each number last.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--controls", nargs="*", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.resolve(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    harness.use_precision(cell.config)
    mod = harness.load_module("drivers", cell.traffic["driver"])
    controls = args.controls or [cell.config["control"]]
    rows = {"program": []} | {c: [] for c in controls}
    for i, seed in enumerate(args.seeds):
        drv = mod.Driver(cell.config, cell.traffic, seed, devices,
                         cell.limits, args.seconds)
        t0 = time.time()
        drv.setup()
        t1 = time.time()
        if args.seconds > 0:
            harness.measure(drv, args.seconds)
        drv.release()
        t2 = time.time()
        got = drv.readings()
        rows["program"].append(got)
        print(json.dumps({"seed": seed, "side": "program", **got,
                          "setup_s": t1 - t0,
                          "reference_s": time.time() - t2,
                          "detail": getattr(drv, "detail", None)}),
              flush=True)
        for c in controls if i < args.control_seeds else ():
            ctl = drv.readings(c)
            rows[c].append(ctl)
            print(json.dumps({"seed": seed, "side": c, **ctl,
                              "detail": getattr(drv, "detail", None)}),
                  flush=True)
        del drv
        gc.collect()
    summary = {side: {k: [max(r[k] for r in rs), min(r[k] for r in rs)]
                      for k in rs[0]} for side, rs in rows.items() if rs}
    print(json.dumps({"summary_max_min": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
