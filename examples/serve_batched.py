"""Serving example: batched autoregressive decoding with the models'
prefill and decode — prefill a batch of prompts, then decode tokens with
the KV/SSM cache, for three different architecture families.

    PYTHONPATH=src python examples/serve_batched.py [--new-tokens 16]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.models import build_model


def serve(arch: str, batch=4, prompt_len=48, new_tokens=16):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    total = prompt_len + new_tokens

    pre_batch = {"tokens": prompts}
    if cfg.family == "encdec":
        pre_batch["src_embeds"] = jax.random.normal(
            key, (batch, prompt_len, cfg.d_model))

    t0 = time.time()
    logits, cache = jax.jit(model.prefill)(params, pre_batch)
    # grow attention caches to the full decode horizon
    def grow(c, k):
        grow_axes = {"dense": ("k", "v"), "moe": ("c_kv", "k_rope", "k", "v"),
                     "vlm": ("k", "v"), "encdec": ("k", "v"),
                     "hybrid": ("shared_k", "shared_v")}
        if k in grow_axes.get(cfg.family, ()) and c.ndim >= 3:
            pad = [(0, 0)] * c.ndim
            pad[2] = (0, new_tokens)
            return jnp.pad(c, pad)
        return c
    cache = {k: grow(v, k) for k, v in cache.items()}
    t_prefill = time.time() - t0

    decode = jax.jit(model.decode)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for pos in range(prompt_len, total):
        logits, cache = decode(params, tok, cache, jnp.int32(pos))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    seqs = jnp.concatenate(out, axis=1)
    print(f"{arch:22s} [{cfg.family:6s}] prefill({batch}x{prompt_len}) "
          f"{t_prefill*1e3:6.0f}ms | {new_tokens} tokens decoded @ "
          f"{t_decode/new_tokens*1e3:6.1f} ms/tok | sample: "
          f"{seqs[0, :8].tolist()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    for arch in ("llama3.2-1b", "rwkv6-7b", "deepseek-v2-lite-16b"):
        serve(arch, new_tokens=args.new_tokens)


if __name__ == "__main__":
    main()
