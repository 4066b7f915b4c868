"""End-to-end driver: FedELMY fine-tuning of an assigned LLM architecture
(~100M-param llama3.2-1b variant) for a few hundred steps across
domain-shifted clients.

    PYTHONPATH=src python examples/fedelmy_llm_finetune.py [--steps 60]

Four clients hold token streams from different Markov domains (synthetic
domain shift). Each client trains a pool of S=2 models with the d1/d2
objective; held-out perplexity of the traveling average is tracked after
every client. This is the production path: the same `repro.api.launch`
and local-phase programs that every architecture trains through, at a
small size.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.api import Experiment, launch
from repro.configs import FedConfig, get_arch
from repro.data import batch_iterator, make_lm_dataset
from repro.models import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60,
                    help="E_local steps per pool model")
    ap.add_argument("--pool", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    args = ap.parse_args()

    # ~100M-param member of the llama3.2 family: 4 layers, d_model 512
    cfg = dataclasses.replace(
        get_arch("llama3.2-1b"), n_layers=4, d_model=512, n_heads=8,
        n_kv_heads=4, d_ff=2048, head_dim=64, vocab_size=8192,
        sliding_window=0, param_dtype="float32")
    model = build_model(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))))
    print(f"arch: llama3.2 family reduced, {n_params/1e6:.1f}M params")

    domains = make_lm_dataset(n_seqs=512, seq_len=args.seq_len,
                              vocab=cfg.vocab_size, n_domains=4, seed=0)
    iters = [batch_iterator({"tokens": d.tokens[:, :-1],
                             "labels": d.tokens[:, 1:]}, 16, seed=i)
             for i, d in enumerate(domains)]
    held = make_lm_dataset(n_seqs=64, seq_len=args.seq_len,
                           vocab=cfg.vocab_size, n_domains=4, seed=99)
    held_batch = {
        "tokens": jnp.concatenate([d.tokens[:16, :-1] for d in held]),
        "labels": jnp.concatenate([d.tokens[:16, 1:] for d in held])}

    @jax.jit
    def neg_ppl(params):
        return -jnp.exp(model.loss_fn(params, held_batch))

    fed = FedConfig(n_clients=4, pool_size=args.pool, e_local=args.steps,
                    e_warmup=max(10, args.steps // 3), learning_rate=3e-4,
                    alpha=0.06, beta=1.0)
    t0 = time.time()
    res = launch(Experiment(model=model, client_iters=iters, fed=fed,
                            strategy="fedelmy", key=jax.random.PRNGKey(0),
                            eval_fn=neg_ppl))
    m = res.params
    for c in res.clients:
        print(f"after client {c.client}: held-out ppl "
              f"{-c.global_metric:.2f}")
    total_steps = fed.e_warmup + 4 * fed.pool_size * fed.e_local
    print(f"final held-out ppl {-float(neg_ppl(m)):.2f} "
          f"(random={cfg.vocab_size}) — {total_steps} total steps, "
          f"{time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
