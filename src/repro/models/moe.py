"""Mixture-of-Experts layer for one device's share of expert parallelism.

The router is f32 over all `moe.n_experts` experts; each token takes the
greedy top-k of the softmax, with the top-k probabilities as gates
(renormalised to sum to one only where `norm_topk_prob` is set, then times
`routed_scaling`). The device holds the first `experts_held` of the
experts: it computes their part of the layer and nothing of the rest, as
one chip of an expert-parallel group does before the exchange.

Dispatch drops no token and has no capacity: the N*k assignments are
sorted by expert, the held experts' first, and their rows gathered; the
held experts run as grouped GEMMs over those rows (`kernels/ops.
grouped_matmul`), whose work scales with the rows that reach them; the
outputs are gathered back and summed with their gates. The shared experts
are added once. The balance loss is DeepSeek-V2's per-sequence one, over
all experts on this device's tokens, which every device computes alike:

    aux = alpha * mean_b sum_e (E / (T k)) count_{b,e} * mean_t P_{b,t,e}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from repro.models.layers import ACC, _he, mlp, mlp_init


def moe_init(key, cfg, dtype):
    m, d = cfg.moe, cfg.d_model
    held, f = cfg.resolved_experts_held, m.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": _he(ks[0], (d, m.n_experts), jnp.float32),
        "w_gate": _he(ks[1], (held, d, f), dtype, fan_in=d),
        "w_up": _he(ks[2], (held, d, f), dtype, fan_in=d),
        "w_down": _he(ks[3], (held, f, d), dtype, fan_in=f),
    }
    if m.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, f * m.n_shared_experts, dtype)
    return p


def route(router, cfg, x):
    """x (B, T, D) -> gates (N, k) f32, expert ids (N, k), balance loss."""
    m = cfg.moe
    b, t, d = x.shape
    logits = jnp.einsum("nd,de->ne", x.reshape(b * t, d).astype(ACC),
                        router, precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * m.routed_scaling
    counts = jax.nn.one_hot(idx.reshape(b, t * m.top_k), m.n_experts,
                            dtype=ACC).sum(1)                   # (B, E)
    load = counts * (m.n_experts / (t * m.top_k))
    aux = m.aux_loss_alpha * jnp.mean(jnp.sum(
        load * probs.reshape(b, t, m.n_experts).mean(1), axis=-1))
    return gates, idx, aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """Rows x[order // k] (each token's k assignments, sorted by expert);
    the backward gathers the rows back and sums each token's k."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], inv


def _dispatch_bwd(k, inv, g):
    gx = g[inv].reshape(g.shape[0] // k, k, -1).astype(ACC).sum(1)
    return gx.astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, order, inv):
    """Rows y[inv]: the sorted rows back in assignment order."""
    return y[inv]


def _combine_fwd(y, order, inv):
    return y[inv], order


def _combine_bwd(order, g):
    return g[order], None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_ffn(p, cfg, x):
    """x: (B, T, D) -> (y (B, T, D), balance loss, rows routed to each held
    expert (H,) int32), for the held experts [0, H)."""
    m = cfg.moe
    b, t, d = x.shape
    n, k, held = b * t, m.top_k, p["w_gate"].shape[0]
    with jax.named_scope(obs.MOE_ROUTE):
        gates, idx, aux = route(p["router"], cfg, x)
    with jax.named_scope(obs.MOE_DISPATCH):
        slot = idx.reshape(-1)                      # held experts first
        order = jnp.argsort(slot, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
        sizes = jnp.sum(slot[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
        rows = _dispatch(x.reshape(n, d), order, inv, k)
    with jax.named_scope(obs.MOE_EXPERTS):
        g = ops.grouped_matmul(rows, p["w_gate"], sizes)
        u = ops.grouped_matmul(rows, p["w_up"], sizes)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        out = ops.grouped_matmul(h, p["w_down"], sizes, x.dtype)
    with jax.named_scope(obs.MOE_DISPATCH):
        back = _combine(out, order, inv).reshape(n, k, d)
        w = jnp.where(slot.reshape(n, k) < held, gates, 0.0)
        y = jnp.einsum("nkd,nk->nd", back.astype(ACC), w)
    if m.n_shared_experts:
        with jax.named_scope(obs.MOE_SHARED):
            y = y + mlp(p["shared"], x).reshape(n, d).astype(ACC)
    return y.reshape(b, t, d).astype(x.dtype), aux, sizes
