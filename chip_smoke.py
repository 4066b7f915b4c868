#!/usr/bin/env python3
"""Bring-up check: FedELMY's train-then-serve path on a TPU chip.

    python chip_smoke.py            # phases A and B on one chip
    python chip_smoke.py --chips 4  # the four-chip fleet phase, alone

Phase A, the paper CNN at the paper's widths (appendix D.5; 32x32x3
inputs, 10 classes): a `fedelmy` chain through `api.launch` with
DataPlan-scanned local phases (Adam), the final pool served through
`PoolServer.from_result`. On a TPU the local phase runs the Pallas im2col
GEMM. Checked: the fused-loss twin against the `lax.conv` forward, loss and
gradients, in f32; finite losses and ensemble scores.

Phase B, llama3.2-1b at its published widths (d_model 2048, d_ff 8192,
vocab 128256, GQA 32/8, bf16 params), depth cut only as far as one chip's
memory forces: one `fedelmy` client with the low-rank pool, the pool served
in factor form (`factored=True`: shared base forward plus tiled BGMV
corrections, the tied unembed included). Its 128-token sequences are a
block of the splash attention kernel, so the local phase's attention and
the factored forward's run it. Checked: the factored scores against a
reference that densifies one member at a time.

--chips 4, the fleet cohort under `shard_map` over four chips
(`launch(FleetSpec, mesh=make_cohort_mesh(cohort))`), against the same run
with mesh=None.

Each phase prints its own lines (compile and step or request seconds,
losses, the difference from its reference, device memory); none of them is
a benchmark metric. A passing run ends with the line
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Without a TPU it exits non-zero before any phase; any failed check raises,
so the exit code is non-zero and that line is not printed.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import Experiment, launch  # noqa: E402
from repro.api import trainer as trainer_mod  # noqa: E402
from repro.api.trainer import LocalTrainer  # noqa: E402
from repro.configs import FedConfig, get_arch  # noqa: E402
from repro.data import (DataPlan, make_image_dataset,  # noqa: E402
                        make_lm_dataset)
from repro.kernels.local_step import fused_loss_for  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_cohort_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.scenarios import get_fleet  # noqa: E402
from repro.serve import PoolServer  # noqa: E402
from repro.sharding import can_shard_flat  # noqa: E402

# Phase A: the im2col GEMM twin and `lax.conv` both run in f32 here
# (precision "highest": the chip's default f32 matmul rounds operands to
# bf16). The deepest contraction is fc1's K = 4096; f32 dot products that
# long drift by about sqrt(K)·eps ≈ 8e-6 of their magnitude, so 1e-4 of the
# largest value leaves a factor of 10 and still catches any wrong tap,
# layout or VJP, which move results by O(1).
CNN_RTOL = 1e-4

# Phase B: both sides run bf16 params and activations with f32
# accumulation. The reference rounds each densified member weight
# (base + U Vᵀ) to bf16, a relative error of up to 2^-9 per weight, and
# both round every projection output to bf16; through the layers that
# moves logits by several times 2^-9 of their scale (1.6% of it on a TPU
# v5e at 14 layers). 2^-5 of the largest reference score bounds that with
# room, while the base model alone (no member deltas) must sit further
# from the reference than the factored scores do, so the check sees the
# deltas.
LLAMA_RTOL = 2.0 ** -5

# Fraction of the device's memory limit the Phase B local-phase program may
# take; the rest covers buffers outside that program and fragmentation.
HBM_FRACTION = 0.95


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def fail(msg: str) -> None:
    raise RuntimeError(msg)


@contextlib.contextmanager
def compile_clock():
    """Seconds the XLA backend spent compiling, and persistent-cache hits,
    from jax.monitoring events while the block runs. A cache hit skips the
    backend compile, so a warm cache shows as fewer compile seconds.
    (Tracing is left out: nested jit traces report overlapping spans.)"""
    c = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
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


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def require_tpu(n_chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{devs[0].platform!r}; no phase was run")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, JAX found "
                 f"{len(devs)}")


def _max_rel(a, b) -> float:
    """max|a − b| over max|b|, across two pytrees."""
    num = max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
              for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    den = max(float(jnp.max(jnp.abs(y.astype(jnp.float32))))
              for y in jax.tree.leaves(b))
    return num / den


def _serve(phase, server, arrays, sizes, seed):
    """Warm every bucket `sizes` uses, then time one request per size.
    Returns the (scores, idx) of the last request."""
    server.warmup(arrays, sizes)
    rng = np.random.default_rng(seed)
    n_rows = next(iter(arrays.values())).shape[0]
    secs = []
    for n in sizes:
        idx = rng.integers(0, n_rows, size=n).astype(np.int32)
        t0 = time.perf_counter()
        scores, preds = server.score(arrays, idx)
        secs.append(time.perf_counter() - t0)
        if not np.isfinite(scores).all():
            fail(f"phase {phase}: non-finite ensemble scores")
        if scores.shape[0] != n or preds.shape[0] != n:
            fail(f"phase {phase}: {scores.shape} scores for {n} requests")
    say(phase, requests=list(sizes), buckets=list(server.buckets),
        request_s_mean=sum(secs) / len(secs), request_s_max=max(secs),
        scores_shape=list(scores.shape))
    return scores, idx


def _train(phase, experiment, n_steps):
    """`launch` the experiment twice: cold (compiles every program), then
    warm (runs them again from their caches), which gives the step time.
    Returns the warm run's result."""
    with compile_clock() as clock:
        t0 = time.perf_counter()
        res = launch(experiment)
        jax.block_until_ready(res.params)
        cold = time.perf_counter() - t0
    del res                       # one model's training state at a time
    gc.collect()
    t0 = time.perf_counter()
    res = launch(experiment)
    jax.block_until_ready(res.params)
    warm = time.perf_counter() - t0
    losses = [m.task_loss for c in res.clients for m in c.models]
    if not losses or not all(math.isfinite(x) for x in losses):
        fail(f"phase {phase}: task losses {losses}")
    say(phase, cold_train_s=cold, compile_s=clock["compile_s"],
        cache_hits=clock["cache_hits"], warm_train_s=warm, steps=n_steps,
        step_s=warm / n_steps, task_losses=[round(x, 4) for x in losses])
    return res


# ---------------------------------------------------------------------------
# Phase A: paper CNN
# ---------------------------------------------------------------------------

def phase_cnn(*, n_clients=3, samples_per_client=256, batch=32, pool_size=2,
              e_local=4, e_warmup=2, n_queries=64, sizes=(1, 5, 8, 32),
              seed=0) -> dict:
    cfg = get_arch("paper-cnn")
    model = build_model(cfg)
    ds = make_image_dataset(n_clients * samples_per_client, seed=seed,
                            noise=2.5)
    plans = [DataPlan({"images": ds.images[i::n_clients],
                       "labels": ds.labels[i::n_clients]}, batch,
                      seed=seed * 100 + i) for i in range(n_clients)]
    fed = FedConfig(n_clients=n_clients, pool_size=pool_size,
                    e_local=e_local, e_warmup=e_warmup, seed=seed)
    say("A", model=cfg.name, clients=n_clients, pool_size=pool_size,
        e_local=e_local, e_warmup=e_warmup, batch=batch,
        optimizer=fed.optimizer)

    res = _train("A", Experiment(model=model, client_iters=plans, fed=fed,
                                 strategy="fedelmy",
                                 key=jax.random.PRNGKey(seed)),
                 e_warmup + n_clients * pool_size * e_local)

    test = make_image_dataset(n_queries, seed=seed + 77, noise=2.5)
    arrays = {"images": jnp.asarray(test.images)}
    server = PoolServer.from_result(model, res)
    _serve("A", server, arrays, sizes, seed)

    # the fused twin the trainer steps through vs the lax.conv forward
    probe = {"images": arrays["images"][:batch],
             "labels": jnp.asarray(test.labels[:batch])}
    twin = fused_loss_for(model.loss_fn)
    if twin is model.loss_fn:
        fail("phase A: the CNN registered no fused-loss twin")
    with jax.default_matmul_precision("highest"):
        lt, gt = jax.jit(jax.value_and_grad(twin))(res.params, probe)
        lc, gc_ = jax.jit(jax.value_and_grad(model.loss_fn))(res.params,
                                                             probe)
    loss_rel = abs(float(lt) - float(lc)) / abs(float(lc))
    grad_rel = _max_rel(gt, gc_)
    say("A", twin_loss=float(lt), conv_loss=float(lc), loss_rel=loss_rel,
        grad_rel=grad_rel, rtol=CNN_RTOL, peak_bytes_in_use=peak_bytes())
    if not (math.isfinite(float(lt)) and loss_rel <= CNN_RTOL
            and grad_rel <= CNN_RTOL):
        fail(f"phase A: fused twin vs lax.conv loss_rel={loss_rel} "
             f"grad_rel={grad_rel} > {CNN_RTOL}")
    return {"loss_rel": loss_rel, "grad_rel": grad_rel}


# ---------------------------------------------------------------------------
# Phase B: llama3.2-1b at published widths
# ---------------------------------------------------------------------------

def local_phase_bytes(cfg, fed, arrays, batch) -> int:
    """Device bytes of the scanned local-phase program (arguments +
    outputs + temporaries) at `cfg`, from the compiler's memory analysis
    for the first device — the largest program of the Phase B run."""
    model = build_model(cfg)
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev)

    params = jax.tree.map(spec, jax.eval_shape(model.init,
                                               jax.random.PRNGKey(0)))
    idx = jax.ShapeDtypeStruct((fed.pool_size, fed.e_local, batch),
                               jnp.int32, sharding=dev)
    hp = jax.ShapeDtypeStruct((), jnp.float32, sharding=dev)
    trainer = LocalTrainer(model.loss_fn, fed)
    m = trainer.scanned_local.lower(params, jax.tree.map(spec, arrays), idx,
                                    hp, hp).compile().memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def reckon_depth(full: int, need, limit: float):
    """The largest depth whose `need(depth)` bytes fit `limit`: `full` if
    it fits; else a line through the full- and half-depth needs (the
    program's bytes grow linearly with depth) picks a depth, and compiles
    step down from there until one fits. Returns (depth, {depth: bytes})."""
    seen = {full: need(full)}
    if seen[full] <= limit:
        return full, seen
    half = max(1, full // 2)
    seen[half] = need(half)
    per_layer = (seen[full] - seen[half]) / max(full - half, 1)
    n = (half + int((limit - seen[half]) // per_layer) if per_layer > 0
         else half)
    n = max(1, min(n, full - 1))
    while seen.setdefault(n, need(n)) > limit:
        n -= 1
        if n < 1:
            fail(f"no depth fits {limit} bytes: {seen}")
    return n, seen


def phase_llama(*, cfg=None, hbm_limit=None, seq_len=128, batch=4, n_seqs=64,
                pool_size=2, e_local=3, e_warmup=2, rank=8, n_queries=16,
                sizes=(1, 5, 8), seed=0) -> dict:
    cfg = cfg or get_arch("llama3.2-1b")
    fed = FedConfig(n_clients=1, pool_size=pool_size, e_local=e_local,
                    e_warmup=e_warmup, learning_rate=1e-3,
                    pool_backend="lowrank", pool_rank=rank, seed=seed)
    text = make_lm_dataset(n_seqs=n_seqs, seq_len=seq_len,
                           vocab=cfg.vocab_size, seed=seed)[0]
    arrays = {"tokens": text.tokens[:, :-1], "labels": text.tokens[:, 1:]}

    if hbm_limit is not None:
        limit = int(HBM_FRACTION * hbm_limit)

        def need(n):
            return local_phase_bytes(dataclasses.replace(cfg, n_layers=n),
                                     fed, arrays, batch)

        with compile_clock() as clock:
            n_layers, seen = reckon_depth(cfg.n_layers, need, limit)
        sizes_seen = ", ".join(f"{n} layers {b}"
                               for n, b in sorted(seen.items()))
        if n_layers < cfg.n_layers:
            say("B", depth_cut=f"{cfg.n_layers}->{n_layers}",
                reason=f"local-phase program bytes ({sizes_seen}) vs "
                       f"{HBM_FRACTION} x bytes_limit {hbm_limit} = {limit}",
                reckon_compile_s=clock["compile_s"])
            cfg = dataclasses.replace(cfg, n_layers=n_layers)
        else:
            say("B", depth_cut="none", reason=f"{sizes_seen} fits {limit}")
    model = build_model(cfg)
    say("B", model=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        d_ff=cfg.d_ff, vocab=cfg.vocab_size,
        heads=f"{cfg.n_heads}/{cfg.n_kv_heads}", dtype=cfg.param_dtype,
        seq_len=seq_len, batch=batch, pool_size=pool_size, rank=rank,
        e_local=e_local, e_warmup=e_warmup, optimizer=fed.optimizer)

    plan = DataPlan(arrays, batch, seed=seed)
    res = _train("B", Experiment(model=model, client_iters=[plan], fed=fed,
                                 strategy="fedelmy",
                                 key=jax.random.PRNGKey(seed)),
                 e_warmup + pool_size * e_local)
    pool = res.require_final_pool()
    del res, plan
    gc.collect()

    queries = make_lm_dataset(n_seqs=n_queries, seq_len=seq_len,
                              vocab=cfg.vocab_size, seed=seed + 77)[0]
    qarrays = {"tokens": jnp.asarray(queries.tokens[:, :-1])}
    server = PoolServer.from_pool(model, pool, factored=True,
                                  buckets=(1, 8))
    with compile_clock() as clock:
        scores, idx = _serve("B", server, qarrays, sizes, seed)
    say("B", serve_compile_s=clock["compile_s"],
        serve_cache_hits=clock["cache_hits"])

    # reference: densify one live member at a time, plain forward,
    # weighted mean with the server's weights
    batch_q = {"tokens": qarrays["tokens"][jnp.asarray(idx)]}
    fwd = jax.jit(model.forward)
    w = np.asarray(server.weights)
    ref = None
    for t in np.flatnonzero(w):
        member = pool.member(int(t))
        term = w[t] * fwd(member, batch_q)
        ref = term if ref is None else ref + term
        del member
    ref = np.asarray(ref / w.sum())
    base = np.asarray(fwd(pool.base, batch_q))
    scale = float(np.abs(ref).max())
    err = float(np.abs(scores - ref).max())
    sep = float(np.abs(base - ref).max())
    say("B", factored_vs_ref_max_abs=err, base_vs_ref_max_abs=sep,
        ref_max_abs=scale, rtol=LLAMA_RTOL, live_members=int((w > 0).sum()),
        peak_bytes_in_use=peak_bytes())
    if not (err <= LLAMA_RTOL * scale and err < sep):
        fail(f"phase B: factored vs reference max|diff|={err} "
             f"(limit {LLAMA_RTOL * scale}, base model alone {sep})")
    return {"err": err, "sep": sep, "scale": scale,
            "n_layers": cfg.n_layers}


# ---------------------------------------------------------------------------
# Four chips: the fleet cohort under shard_map
# ---------------------------------------------------------------------------

# The sharded program runs each device's slice of the cohort as its own
# vmapped batch (2 clients per device instead of 8 in one program), and the
# compiler may block those GEMMs differently, so the two runs agree to f32
# reassociation, compounded over a few momentum steps: 1e-4 of the largest
# parameter.
FLEET_RTOL = 1e-4


def phase_fleet(*, n_devices=4, fleet_name="fleet_smoke", rounds=2,
                e_local=4) -> dict:
    model = build_model(get_arch("paper-cnn"))
    fleet = get_fleet(fleet_name).replace(rounds=rounds)
    fed = FedConfig(e_local=e_local, seed=fleet.seed)
    mesh = make_cohort_mesh(fleet.cohort_size)
    if mesh.devices.size != n_devices or \
            not can_shard_flat(mesh, fleet.cohort_size):
        fail(f"fleet: cohort {fleet.cohort_size} does not shard over "
             f"{n_devices} devices (mesh {dict(mesh.shape)}); the engine "
             "would fall back to one device")
    say("F", fleet=fleet.name, cohort=fleet.cohort_size, rounds=rounds,
        strategy=fleet.strategy, mesh=dict(mesh.shape), e_local=e_local)

    with compile_clock() as clock:
        t0 = time.perf_counter()
        sharded = launch(fleet, model, fed=fed, mesh=mesh)
        jax.block_until_ready(sharded.params)
        wall = time.perf_counter() - t0
    programs = [fn for (_, m, _, _), fn in trainer_mod._SHARDED_CACHE.items()
                if m is mesh and fn._cache_size() > 0]
    say("F", path="shard_map", train_s=wall, compile_s=clock["compile_s"],
        cache_hits=clock["cache_hits"], sharded_programs=len(programs),
        metric=sharded.final_metric)
    with compile_clock() as clock:
        t0 = time.perf_counter()
        single = launch(fleet, model, fed=fed, mesh=None)
        jax.block_until_ready(single.params)
        wall = time.perf_counter() - t0
    say("F", path="mesh=None", train_s=wall, compile_s=clock["compile_s"],
        metric=single.final_metric)

    mesh_devs = set(mesh.devices.flat)
    out_devs = {d for a in jax.tree.leaves(sharded.params)
                for d in a.sharding.device_set}
    single_devs = {d for a in jax.tree.leaves(single.params)
                   for d in a.sharding.device_set}
    rel = _max_rel(sharded.params, single.params)
    say("F", sharded_output_devices=len(out_devs),
        single_output_devices=len(single_devs), params_rel=rel,
        rtol=FLEET_RTOL, peak_bytes_in_use=peak_bytes())
    if not programs:
        fail("fleet: no shard_map program ran on the cohort mesh")
    if out_devs != mesh_devs:
        fail(f"fleet: outputs on {len(out_devs)} devices, mesh has "
             f"{len(mesh_devs)}")
    if not rel <= FLEET_RTOL:
        fail(f"fleet: sharded vs mesh=None params differ by {rel}")
    return {"params_rel": rel, "devices": len(out_devs)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fleet cohort sharded over four "
                         "chips, against mesh=None")
    args = ap.parse_args(argv)
    require_tpu(args.chips)
    say("main", compile_cache=enable_compile_cache(),
        devices=len(jax.devices()), kind=jax.devices()[0].device_kind)
    if args.chips == 4:
        phase_fleet(n_devices=4)
    else:
        phase_cnn()
        gc.collect()
        stats = jax.devices()[0].memory_stats() or {}
        phase_llama(hbm_limit=stats.get("bytes_limit"))
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
