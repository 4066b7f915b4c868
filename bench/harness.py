"""One run of one benchmark cell: set-up, the measured window, the check,
and the result line.

Everything that belongs to one configuration, traffic mix, driver or metric
lives in a file of its own, found here by the name that BENCHMARK.json
gives it:

    bench/configs/<config>.json      sizes, source, cuts, deployment
    bench/traffic/<traffic>.json     the mix's parameters, and its driver
    bench/drivers/<driver>.py        builds the timed path, runs one unit
    bench/metrics/<metric>.py        reads one metric from the run record
    bench/limits/<cell>.json         the limit of each number the check
                                     compares, with the readings behind it

A driver module defines ``Driver(config, traffic, seed, devices, limits,
seconds)`` with ``setup()``, ``unit() -> work`` (one call into the
program, ending in host arrays or ``block_until_ready``), ``release()``
(frees the program's state) and ``readings(control=None) -> {name:
value}``: the numbers the check can compare, of the program's run
against the plain reference, or with ``control`` of that control against
the reference; the cell's limits file names the ones it does compare.
A metric module defines ``read(rec) -> float | None``; None leaves the
metric out of the line.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, NamedTuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# A traced run profiles whole units for at least this long (or the whole
# window, where --seconds is shorter): long enough to hold several units
# of the short cells, short enough that the trace stays small.
TRACE_MIN_S = 3.0


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module; names may hold dots and dashes."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no file {path.relative_to(ROOT)} for "
                         f"{kind} {name!r}")
    mod_name = "bench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell(NamedTuple):
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, float]


def resolve(name: str, bench: Dict[str, Any] = None) -> Cell:
    """A `workloads` name → its configuration, traffic and metrics, read
    from the files that BENCHMARK.json names."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    limits = {k: v["limit"] for k, v in
              load_json(BENCH / "limits" / f"{name}.json").items()}
    return Cell(name, config, traffic, int(w["chips"]), e2e, per_layer,
                limits)


# ---------------------------------------------------------------------------
# Device, peaks, compile cache, compile counting
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    """A PRNG key for any whole number (the driver's seeds exceed 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def arch_config(config: Dict[str, Any]):
    """The repository's architecture entry with the configuration's
    overrides."""
    import dataclasses
    from repro.configs import get_arch
    return dataclasses.replace(get_arch(config["arch"]),
                               **config.get("overrides", {}))


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found platform "
                         f"{devs[0].platform!r}. Nothing was measured.")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devs)}. Nothing was measured.")
    return devs[:n]


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} "
                         f"in bench/peaks.json")
    return table[device_kind]


def use_precision(config: Dict[str, Any]) -> None:
    """The configuration's `matmul_precision` as JAX's default for every
    product the program traces from here on (absent: JAX's own)."""
    import jax
    jax.config.update("jax_default_matmul_precision",
                      config.get("matmul_precision"))


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR points), for every
    program however quickly it compiled, so that a second run of a cell
    compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@contextlib.contextmanager
def compile_clock():
    """Backend compiles, their seconds, and persistent-cache hits while the
    block runs, from jax.monitoring events. A cache hit skips the backend
    compile, so a warm cache shows as no compiles."""
    import jax
    c = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            c["compiles"] += 1
            c["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            c["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield c
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def span(name: str):
    """A host span of the benchmark's own, written into the profiler's
    trace when one is being taken (idle gaps on the device are labelled by
    the innermost such span)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

def measure(drv, seconds: float) -> Dict[str, Any]:
    """Run whole units until `seconds` have passed; the window closes at the
    end of the first unit that ends after that. Every unit ends in host
    arrays or `block_until_ready` (the driver's `unit` contract)."""
    units, work = [], 0
    t0 = last = time.perf_counter()
    while True:
        with span("unit"):
            work += drv.unit()
        t = time.perf_counter()
        units.append(t - last)
        last = t
        if t - t0 >= seconds:
            break
    return {"seconds": last - t0, "units": units, "work": work}


def traced_window(drv, seconds: float) -> Dict[str, Any]:
    import jax
    from bench import trace as trace_mod
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tdir)
        try:
            win = measure(drv, seconds)
        finally:
            jax.profiler.stop_trace()
        win["trace"] = trace_mod.reduce(trace_mod.load_dir(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return win


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------

def main(args, t_start: float) -> int:
    cell = resolve(args.workload)
    devices = require_chips(cell.chips)
    peaks = peaks_for(devices[0].device_kind)
    cache = enable_compile_cache()
    say(f"compile cache {cache}")
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                    peaks, t_start)
    print(json.dumps(line), flush=True)
    for n, c in line["checks"].items():
        print(f"check {n} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             peaks: Dict[str, Any], t_start: float) -> Dict[str, Any]:
    """Set-up, window, metrics and check of one run; returns the result
    line (its "checks" key last)."""
    kind = devices[0].device_kind
    window = min(seconds, TRACE_MIN_S) if trace else seconds
    use_precision(cell.config)
    driver_mod = load_module("drivers", cell.traffic["driver"])
    drv = driver_mod.Driver(cell.config, cell.traffic, seed, devices,
                            cell.limits, window)
    with compile_clock() as clock:
        drv.setup()
        setup_s = time.time() - t_start
        say(f"cell={cell.name} seed={seed} device={kind} x{len(devices)} "
            f"setup_s={setup_s:.3f} compiles={clock['compiles']} "
            f"compile_s={clock['compile_s']:.3f} "
            f"cache_hits={clock['cache_hits']}")
        before = clock["compiles"]
        win = traced_window(drv, window) if trace else measure(drv, window)
        in_window = clock["compiles"] - before
    say(f"window_s={win['seconds']:.3f} units={len(win['units'])} "
        f"work={win['work']} compiles_in_window={in_window}")
    peak = memory_peak_bytes(devices)

    rec = {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
           "chips": len(devices), "peaks": peaks, "setup_s": setup_s,
           "window": win, "driver": drv}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    attempted = getattr(drv, "attempted", len(win["units"]))
    line = {"correct": False, "attempted": attempted, "failed": 0,
            "metrics": metrics, "device": device}
    if trace:
        tr = win["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"][:10],
                             "idle_gaps": tr["idle_gaps"][:10]}
    del rec, win
    drv.release()
    t0 = time.time()
    checks = [(n, v, cell.limits[n]) for n, v in drv.readings().items()
              if n in cell.limits]
    say(f"check_s={time.time() - t0:.3f}")
    line["correct"] = in_window == 0 and all(
        math.isfinite(v) and v <= lim for _, v, lim in checks)
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    line["checks"]["compiles_in_window"] = {"value": in_window, "limit": 0}
    return line
