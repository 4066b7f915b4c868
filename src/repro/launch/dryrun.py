import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination against the production mesh with ShapeDtypeStruct inputs — no
allocation, no execution. Proves the distribution config is coherent and
captures memory_analysis / cost_analysis / collective schedule for
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline
from repro.configs import ARCHS, INPUT_SHAPES, FedConfig, get_arch
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.sharding import batch_specs, cache_specs, param_specs

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _sharding_tree(spec_tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def in_shardings_for(cfg, shape, specs, mesh):
    """Assemble the in_shardings pytree matching input_specs(cfg, shape)."""
    out = {}
    for k, v in specs.items():
        if k in ("params",):
            out[k] = param_specs(v, mesh)
        elif k == "opt_state":
            out[k] = param_specs(v, mesh)
        elif k == "pool":
            if hasattr(v, "members"):          # exact ModelPool
                out[k] = type(v)(param_specs(v.members, mesh), P())
            else:                              # MomentPool
                out[k] = type(v)(param_specs(v.mean, mesh), P(), P(),
                                 param_specs(v.anchor, mesh))
        elif k == "batch":
            out[k] = batch_specs(v, mesh)
        elif k == "token":
            out[k] = batch_specs(v, mesh)
        elif k == "cache":
            out[k] = cache_specs(v, mesh)
        else:                                  # scalars: pos, step
            out[k] = P()
    return out


def dryrun_one(arch: str, shape_name: str, mesh_kind: str,
               save: bool = True, verbose: bool = True,
               tag: str = "", extra_env=None, cfg_override=None) -> dict:
    cfg = cfg_override or get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, why = S.shape_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "tag": tag, "timestamp": time.time()}
    for k, v in (extra_env or {}).items():
        os.environ[k] = v
    if not ok:
        rec.update(status="skipped", reason=why)
        if save:
            _save(rec)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    try:
        specs = S.input_specs(cfg, shape)
        step = S.make_step(cfg, shape)
        shardings = in_shardings_for(cfg, shape, specs, mesh)
        order = list(specs)                      # kwargs -> positional

        def _compile(unroll_env):
            os.environ["REPRO_SCAN_UNROLL"] = unroll_env
            for k, v in (extra_env or {}).items():
                os.environ[k] = v
            # inner scans (attention KV blocks, GLA chunks, loss chunks)
            # fully unroll with coarsened tiles so their cost lands inside
            # the layer body the two-pass correction scales (scan_util.py)
            os.environ["REPRO_INNER_UNROLL"] = "full"
            os.environ["REPRO_ATTN_BLOCK"] = "2048"
            os.environ["REPRO_GLA_CHUNK"] = "256"
            with mesh:
                jitted = jax.jit(
                    lambda *a: S.make_step(cfg, shape)(
                        **dict(zip(order, a))),
                    in_shardings=tuple(
                        jax.tree.map(lambda s: NamedSharding(mesh, s),
                                     shardings[k],
                                     is_leaf=lambda x: isinstance(x, P))
                        for k in order))
                lowered = jitted.lower(*[specs[k] for k in order])
                return lowered.compile()

        # Two-pass layer-cost correction: XLA cost analysis counts a while
        # body ONCE regardless of trip count, so scanned layers would be
        # undercounted ~L×. Pass A: rolled (outside + 1 body). Pass B:
        # unroll=2 (outside + 2 bodies). corrected = A + (L-1)·(B-A).
        t0 = time.time()
        compiled = _compile("")                  # rolled — deployment graph
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost_a = compiled.cost_analysis()
        coll_a = roofline.collective_bytes(compiled.as_text())
        t1 = time.time()
        compiled_b = _compile("2")
        t_compile_b = time.time() - t1
        cost_b = compiled_b.cost_analysis()
        coll_b = roofline.collective_bytes(compiled_b.as_text())
        for k in ("REPRO_SCAN_UNROLL", "REPRO_INNER_UNROLL", "REPRO_ATTN_BLOCK",
                  "REPRO_GLA_CHUNK", *(extra_env or {})):
            os.environ.pop(k, None)

        # per-scan trip count: the B−A delta is "one extra iteration of every
        # layer scan"; trips = iterations per scan (segment length for the
        # hybrid's segmented scans, n_layers otherwise — enc/dec scans of the
        # encdec arch share the same length so one multiplier serves both).
        if cfg.shared_attn_every:
            trips = cfg.shared_attn_every
        else:
            trips = cfg.n_layers
        # clamp: tiny bodies (1-token decode) can fuse differently between
        # passes, making B−A slightly negative — corrected is at least the
        # rolled measurement
        cost = {k: max(float(cost_a.get(k, 0.0)) + (trips - 1) * (
                    float(cost_b.get(k, 0.0)) - float(cost_a.get(k, 0.0))),
                    float(cost_a.get(k, 0.0)))
                for k in ("flops", "bytes accessed", "transcendentals")}
        coll = {k: max(int(coll_a[k] + (trips - 1) * (coll_b[k] - coll_a[k])),
                       coll_a[k])
                for k in coll_a}
        hlo = compiled.as_text()
        n_params = _count_params(specs["params"])
        n_active = roofline.active_params(cfg, n_params)
        terms = roofline.roofline_terms(cost, sum(coll.values()), n_chips)
        mf = roofline.model_flops(cfg, shape, n_params, n_active)
        t_lower = t_compile_b
        rec.update(
            status="ok", n_chips=n_chips, scan_trips=trips,
            cost_raw_rolled={k: float(cost_a.get(k, 0.0))
                             for k in ("flops", "bytes accessed")},
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            n_params=n_params, n_active_params=n_active,
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
                              + (getattr(mem, "argument_size_in_bytes", 0) or 0),
            },
            cost={k: cost.get(k) for k in
                  ("flops", "bytes accessed", "transcendentals")},
            collectives=coll,
            roofline=terms,
            model_flops_global=mf,
            model_flops_per_chip=mf / n_chips,
            useful_flops_ratio=(mf / n_chips) / max(
                terms["hlo_flops_per_device"], 1.0),
            dominant=roofline.dominant_term(terms),
        )
    except Exception as e:                       # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    if verbose:
        if rec["status"] == "ok":
            print(f"[ok] {arch} × {shape_name} × {mesh_kind}: "
                  f"compile {rec['compile_s']}s, dominant={rec['dominant']}, "
                  f"compute={rec['roofline']['compute_s']:.2e}s "
                  f"memory={rec['roofline']['memory_s']:.2e}s "
                  f"collective={rec['roofline']['collective_s']:.2e}s",
                  flush=True)
        else:
            print(f"[{rec['status']}] {arch} × {shape_name} × {mesh_kind}: "
                  f"{rec.get('reason', rec.get('error', ''))[:200]}",
                  flush=True)
    if save:
        _save(rec)
    return rec


def _count_params(param_shapes) -> int:
    import numpy as np
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(param_shapes)))


def _save(rec):
    out = OUT_DIR if not rec.get("tag") else os.path.join(
        OUT_DIR, "..", "hillclimb")
    os.makedirs(out, exist_ok=True)
    suffix = f"__{rec['tag']}" if rec.get("tag") else ""
    name = (f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json"
            ).replace("/", "_")
    with open(os.path.join(out, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="", help="hillclimb variant label")
    ap.add_argument("--env", action="append", default=[],
                    help="KEY=VAL hillclimb lever, repeatable")
    args = ap.parse_args()
    extra_env = dict(kv.split("=", 1) for kv in args.env)

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not (args.all or args.arch):
        ap.error("pass --arch or --all")

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                fname = os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh}.json")
                if args.skip_existing and os.path.exists(fname):
                    with open(fname) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            continue
                rec = dryrun_one(arch, shape, mesh, tag=args.tag,
                                 extra_env=extra_env)
                n_fail += rec["status"] == "error"
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
