"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per benchmark (harness contract).

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --quick    # CI-scale
  PYTHONPATH=src python -m benchmarks.run --only table1_accuracy
  PYTHONPATH=src python -m benchmarks.run --list     # enumerate suite
  PYTHONPATH=src python -m benchmarks.run --quick --json out.json
      # + per-benchmark us_per_call as JSON (the perf-regression guard:
      # scripts/bench_compare.py diffs it against BENCH_baseline.json)
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import traceback

# Module names under benchmarks/; each exposes a run() entry point. --list
# and the suite are both derived from this tuple.
BENCHMARKS = ("table1_accuracy", "table2_fewshot", "table3_ablation",
              "table4_order", "fig5_comm_cost", "fig6_compute_matched",
              "fig9_distance_measures", "fig10_pool_heatmap", "table9_pfl",
              "scenario_grid", "local_phase", "local_phase_cnn",
              "serving", "fleet_throughput",
              "pool_memory")


def _list() -> None:
    """Enumerate registered benchmarks, architecture configs, strategies
    (with their plan topology/aggregation), pool backends, scenarios, and
    partitioners."""
    from repro.api import describe_strategies, list_pool_backends
    from repro.configs import ARCHS
    from repro.scenarios import (get_fleet, get_scenario, list_fleets,
                                 list_partitioners, list_scenarios)
    from repro.serve import get_traffic, list_traffics
    print("benchmarks:")
    for name in BENCHMARKS:
        print(f"  {name}")
    print("configs (archs):")
    for name, cfg in ARCHS.items():
        print(f"  {name} (family={cfg.family}, layers={cfg.n_layers}, "
              f"d_model={cfg.d_model})")
    print("strategies (plans):")
    for name, d in describe_strategies().items():
        print(f"  {name} (topology={d['topology']}, "
              f"local={d['local_block']}, aggregate={d['aggregate']}, "
              f"broadcast={d['broadcast']}, batched={d['batched']})")
    print("pool backends:")
    for name in list_pool_backends():
        print(f"  {name}")
    print("scenarios:")
    for name in list_scenarios():
        spec = get_scenario(name)
        print(f"  {name} ({spec.family}, partitioner={spec.partitioner})")
    print("partitioners:")
    for name in list_partitioners():
        print(f"  {name}")
    print("fleets:")
    for name in list_fleets():
        spec = get_fleet(name)
        print(f"  {name} (fleet_size={spec.fleet_size}, "
              f"cohort={spec.cohort_size}, rounds={spec.rounds}, "
              f"participation={spec.participation})")
    print("traffic specs:")
    for name in list_traffics():
        spec = get_traffic(name)
        print(f"  {name} (arrival={spec.arrival}, "
              f"client_mix={spec.client_mix})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced scale (smoke)")
    ap.add_argument("--only", default=None,
                    help="benchmark name, or a comma-separated list")
    ap.add_argument("--list", action="store_true",
                    help="list registered benchmarks/strategies and exit")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write per-benchmark us_per_call as JSON")
    args = ap.parse_args()

    if args.list:
        _list()
        return

    from benchmarks import common
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.quick:
        common.set_scale("quick")

    names = args.only.split(",") if args.only else list(BENCHMARKS)
    unknown = [n for n in names if n not in BENCHMARKS]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown!r}; see --list")
    suite = {name: importlib.import_module(f"benchmarks.{name}").run
             for name in names}
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            suite[name]()
        except Exception:                       # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            print(f"{name},0,FAILED")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"scale": "quick" if args.quick else "full",
                       "benchmarks": common.TIMINGS}, f, indent=1)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
