"""Training launcher: FedELMY over any assigned architecture.

  PYTHONPATH=src python -m repro.launch.train --arch paper-cnn \
      --clients 4 --pool 3 --e-local 20 [--method fedseq|fedelmy|...]
      [--handoff-dir /tmp/handoff]   # serialize client→client transfers

Each client's local training runs on the local devices (launch/mesh.py).
The --handoff-dir flag exercises the checkpoint-based transfer path (the
actual wire format between pods/sites); omitted, handoffs stay in memory.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Experiment, launch, list_strategies
from repro.checkpoint import load_pytree, save_pytree
from repro.configs import FedConfig, get_arch
from repro.data import (DataPlan, dirichlet_partition, make_domain_datasets,
                        make_image_dataset, make_lm_dataset)
from repro.data.partition import domain_shift_partition
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model


def build_clients(args, cfg):
    if cfg.family == "cnn":
        if args.distribution == "label-skew":
            ds = make_image_dataset(args.samples, seed=args.seed, noise=2.5)
            parts = dirichlet_partition(ds.labels, args.clients,
                                        args.dirichlet_beta, seed=args.seed)
            clients = [{"images": ds.images[p], "labels": ds.labels[p]}
                       for p in parts]
        else:
            doms = make_domain_datasets(args.samples // 4, seed=args.seed)
            cs = domain_shift_partition(doms, args.clients)
            clients = [{"images": c.images, "labels": c.labels} for c in cs]
        test = make_image_dataset(args.samples // 4, seed=args.seed + 77,
                                  noise=2.5)
        test_batch = {"images": jnp.asarray(test.images),
                      "labels": jnp.asarray(test.labels)}
    else:
        doms = make_lm_dataset(n_seqs=args.samples // 64 * 64 or 64,
                               seq_len=args.seq_len,
                               vocab=cfg.vocab_size, n_domains=args.clients,
                               seed=args.seed)
        clients = [{"tokens": d.tokens[:, :-1], "labels": d.tokens[:, 1:]}
                   for d in doms]
        hold = make_lm_dataset(n_seqs=64, seq_len=args.seq_len,
                               vocab=cfg.vocab_size, n_domains=1,
                               seed=args.seed + 77)[0]
        test_batch = {"tokens": jnp.asarray(hold.tokens[:64, :-1]),
                      "labels": jnp.asarray(hold.tokens[:64, 1:])}
    # device-resident scan-routed plans, bit-identical to the
    # batch_iterator streams on these seeds. Conv models included: their
    # losses lower as im2col + blocked GEMM (kernels/local_step.py), so
    # the old conv-in-scan carve-out is gone (DESIGN.md §9)
    iters = [DataPlan(c, args.batch, seed=args.seed * 100 + i)
             for i, c in enumerate(clients)]
    return iters, test_batch


def arch_config(name: str, reduced: bool):
    """The named architecture at its published widths, or its smoke-scale
    variant when `reduced` (the paper CNN has none)."""
    cfg = get_arch(name)
    return cfg.reduced() if reduced and cfg.family != "cnn" else cfg


def make_eval(model, cfg, test_batch):
    if cfg.family == "cnn":
        @jax.jit
        def acc(params):
            logits = model.forward(params, test_batch)
            return jnp.mean(jnp.argmax(logits, -1) == test_batch["labels"])
        return acc

    from repro.models.transformer import lm_eval_fn
    return lm_eval_fn(model, test_batch)            # higher is better


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-cnn")
    ap.add_argument("--method", default="fedelmy",
                    choices=list_strategies())
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--pool", type=int, default=3)
    ap.add_argument("--e-local", type=int, default=20)
    ap.add_argument("--e-warmup", type=int, default=10)
    ap.add_argument("--shots", type=int, default=1)
    ap.add_argument("--alpha", type=float, default=0.06)
    ap.add_argument("--beta", type=float, default=1.0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--batch", type=int, default=48)
    ap.add_argument("--samples", type=int, default=4000)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale arch variant")
    ap.add_argument("--moment-form", action="store_true")
    ap.add_argument("--pool-backend", default=None,
                    help="pool representation: stacked | moment | lowrank "
                         "(default stacked; lowrank is the "
                         "transformer-scale factor pool)")
    ap.add_argument("--pool-rank", type=int, default=8,
                    help="rank ceiling for --pool-backend lowrank")
    ap.add_argument("--distribution", default="label-skew",
                    choices=["label-skew", "domain-shift"])
    ap.add_argument("--dirichlet-beta", type=float, default=0.5)
    ap.add_argument("--handoff-dir", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = arch_config(args.arch, args.reduced)
    model = build_model(cfg)
    iters, test_batch = build_clients(args, cfg)
    eval_fn = make_eval(model, cfg, test_batch)
    backend = args.pool_backend or (
        "moment" if args.moment_form else "stacked")
    fed = FedConfig(n_clients=args.clients, pool_size=args.pool,
                    e_local=args.e_local, e_warmup=args.e_warmup,
                    alpha=args.alpha, beta=args.beta,
                    learning_rate=args.lr,
                    pool_backend=backend, pool_rank=args.pool_rank,
                    distance_measure=("squared_l2" if backend == "moment"
                                      else "l2"),
                    seed=args.seed)

    t0 = time.time()
    method = args.method
    if method == "fedelmy" and args.shots > 1:
        method = "fedelmy_fewshot"
    track_eval = eval_fn if method.startswith("fedelmy") else None
    res = launch(Experiment(model=model, client_iters=iters, fed=fed,
                            strategy=method,
                            key=jax.random.PRNGKey(args.seed),
                            eval_fn=track_eval, shots=args.shots))
    m, hist = res.params, res.history()
    score = (res.final_metric if res.final_metric is not None
             else float(eval_fn(m)))
    wall = time.time() - t0

    if args.handoff_dir:          # exercise the serialized transfer format
        os.makedirs(args.handoff_dir, exist_ok=True)
        path = os.path.join(args.handoff_dir, "m_final.npz")
        save_pytree(path, m)
        m2 = load_pytree(path, jax.tree.map(jnp.zeros_like, m))
        assert all(np.allclose(a, b) for a, b in
                   zip(jax.tree.leaves(m), jax.tree.leaves(m2)))
        print(f"handoff checkpoint: {path} "
              f"({os.path.getsize(path)/1e6:.1f} MB)")

    metric = "acc" if cfg.family == "cnn" else "-nll"
    print(f"method={args.method} arch={args.arch} {metric}={score:.4f} "
          f"wall={wall:.1f}s")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"method": args.method, "arch": args.arch,
                       metric: score, "wall_s": wall, "history": hist}, f,
                      indent=1, default=float)


if __name__ == "__main__":
    main()
