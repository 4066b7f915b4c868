"""Mixture-of-Experts layer for one device's share of expert parallelism.

The router is f32 over all `moe.n_experts` experts; each token takes the
greedy top-k of the softmax, with the top-k probabilities as gates
(renormalised to sum to one only where `norm_topk_prob` is set, then times
`routed_scaling`). The device holds the first `experts_held` of the
experts: it computes their part of the layer and nothing of the rest, as
one chip of an expert-parallel group does before the exchange.

Dispatch drops no token and has no capacity factor: the N*k assignments
are sorted by expert, the held experts' first, and the held path runs over
C of the sorted rows, C the smallest rung of a short static ladder that
holds the H rows routed to the held experts (`held_rungs`; the last rung,
N * min(k, held), holds any routing). The held experts run as grouped
GEMMs over those rows (`kernels/ops.grouped_matmul`), whose work scales
with H; the SwiGLU and every buffer scale with C, not with N*k. Each
token's k outputs are gathered back from the compact rows and summed with
their gates in f32. The shared experts are added once. The balance loss
is DeepSeek-V2's per-sequence one, over all experts on this device's
tokens, which every device computes alike:

    aux = alpha * mean_b sum_e (E / (T k)) count_{b,e} * mean_t P_{b,t,e}
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

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


def held_rungs(n: int, k: int, held: int, n_experts: int
               ) -> Tuple[int, ...]:
    """The row extents the held path runs at, smallest first, for n tokens
    of k assignments and `held` of `n_experts` experts on the device. The
    last, n * min(k, held), holds every routing (a token reaches at most
    min(k, held) held experts), so no rung drops a token. Below it at most
    two rungs, multiples of `ops.GMM_TM`: 1.25 x the rows an even routing
    sends the held experts (n k held / E), and the geometric mean of that
    and the last. A device that holds every expert has the one rung n k."""
    last = n * min(k, held)
    first = _round_up(math.ceil(1.25 * n * k * held / n_experts))
    if held >= n_experts or first >= last:
        return (last,)
    mid = _round_up(math.ceil(math.sqrt(first * last)))
    return (first, mid, last) if mid < last else (first, last)


def held_capacity(n: int, k: int, held: int, n_experts: int, h: int) -> int:
    """The rows the held path runs at when h rows reach the held experts:
    the smallest rung that holds them."""
    for c in held_rungs(n, k, held, n_experts):
        if h <= c:
            return c
    raise ValueError(f"{h} rows exceed the {n} tokens' held assignments")


def _round_up(rows: int) -> int:
    return -(-rows // ops.GMM_TM) * ops.GMM_TM


def _switch(rungs, sizes, branches, *operands):
    """The branch of the smallest rung that holds the routed rows; no
    switch where there is one rung."""
    if len(branches) == 1:
        return branches[0](*operands)
    rung = jnp.sum(jnp.sum(sizes) > jnp.asarray(rungs[:-1], jnp.int32))
    return jax.lax.switch(rung, branches, *operands)


def _gather_sum(rows, pos, w=None):
    """sum_j rows[pos[:, j]] (times w[:, j]) in f32, (N, d): each token's k
    rows from the compact buffer; a position past its rows reads zero."""
    y = 0.0
    for j in range(pos.shape[1]):
        r = rows.at[pos[:, j]].get(mode="fill", fill_value=0).astype(ACC)
        y = y + (r if w is None else r * w[:, j, None])
    return y


def _swiglu(g, u, dtype):
    return (jax.nn.silu(g) * u).astype(dtype)


def _experts(rows, w_gate, w_up, w_down, sizes):
    """The held experts' SwiGLU over the sorted rows -> (out, g, u)."""
    with jax.named_scope(obs.MOE_EXPERTS):
        g = ops.grouped_matmul(rows, w_gate, sizes)
        u = ops.grouped_matmul(rows, w_up, sizes)
        out = ops.grouped_matmul(_swiglu(g, u, rows.dtype), w_down, sizes,
                                 rows.dtype)
    return out, g, u


def _experts_vjp(rows, g, u, w_gate, w_up, w_down, sizes, dout):
    """-> the cotangents of (rows, w_gate, w_up, w_down) from the forward's
    g and u; the GEMMs' forwards are not run again."""
    def gmm_vjp(a, w, dtype=ACC):
        return jax.vjp(lambda a, w: ops.grouped_matmul(a, w, sizes, dtype),
                       a, w)[1]

    with jax.named_scope(obs.MOE_EXPERTS):
        h, swiglu_vjp = jax.vjp(lambda g, u: _swiglu(g, u, rows.dtype), g, u)
        dh, d_down = gmm_vjp(h, w_down, rows.dtype)(dout)
        dg, du = swiglu_vjp(dh)
        drows_g, d_gate = gmm_vjp(rows, w_gate)(dg)
        drows_u, d_up = gmm_vjp(rows, w_up)(du)
    return drows_g + drows_u, d_gate, d_up, d_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held(rungs, x, w, w_gate, w_up, w_down, pos, order, sizes):
    """The held experts' part of the layer, (N, d) f32, over C rows, the
    rung that holds the routed rows: rows x[order[:C] // k] (sorted by
    expert, the held experts' first), the grouped SwiGLU, and each token's
    k outputs gathered back from positions `pos` (N, k) (past C for an
    expert not held) and summed with their gates w (N, k) in f32.

    Its own VJP, so that each rung's branch keeps residuals of one shape:
    the first rung's buffers, which its backward reads; the higher rungs
    run their forward again in the backward. A plain `switch` under `grad`
    would keep every branch's residuals, zero-filled at the last rung's
    rows. Every cotangent is a gather."""
    return _held_fwd(rungs, x, w, w_gate, w_up, w_down, pos, order,
                     sizes)[0]


def _held_fwd(rungs, x, w, w_gate, w_up, w_down, pos, order, sizes):
    k = pos.shape[1]

    def at(c):
        def branch():
            with jax.named_scope(obs.MOE_DISPATCH):
                rows = x[order[:c] // k]
            kept = _experts(rows, w_gate, w_up, w_down, sizes)
            with jax.named_scope(obs.MOE_DISPATCH):
                y = _gather_sum(kept[0], pos, w)
            if c != rungs[0]:
                kept = jax.tree.map(lambda a: jnp.zeros(
                    (rungs[0],) + a.shape[1:], a.dtype), kept)
            return y, kept
        return branch

    y, kept = _switch(rungs, sizes, [at(c) for c in rungs])
    return y, (x, w, w_gate, w_up, w_down, pos, order, sizes, kept)


def _held_bwd(rungs, res, dy):
    x, w, w_gate, w_up, w_down, pos, order, sizes, kept = res
    k = pos.shape[1]

    def at(first, c):
        def branch():
            with jax.named_scope(obs.MOE_DISPATCH):
                token = order[:c] // k
                rows = x[token]
                dy_c = dy[token]
                gate = w.reshape(-1)[order[:c]]     # zero past the held rows
                dout = (dy_c * gate[:, None]).astype(x.dtype)
            out, g, u = (kept if first else
                         _experts(rows, w_gate, w_up, w_down, sizes))
            with jax.named_scope(obs.MOE_DISPATCH):
                dgate = jnp.sum(out.astype(ACC) * dy_c, axis=-1)
                dw = dgate.at[pos].get(mode="fill", fill_value=0)
            drows, d_gate, d_up, d_down = _experts_vjp(
                rows, g, u, w_gate, w_up, w_down, sizes, dout)
            with jax.named_scope(obs.MOE_DISPATCH):
                dx = _gather_sum(drows, pos).astype(x.dtype)
            return dx, dw, d_gate, d_up, d_down
        return branch

    grads = _switch(rungs, sizes, [at(i == 0, c) for i, c in enumerate(rungs)])
    return grads + (None, None, None)


_held.defvjp(_held_fwd, _held_bwd)


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
        sizes = jnp.sum(slot[None, :] == jnp.arange(held)[:, None], axis=1,
                        dtype=jnp.int32)
        mine = (slot < held).reshape(n, k)
        pos = jnp.where(mine, inv.reshape(n, k), n * k)
        w = jnp.where(mine, gates, 0.0)
    y = _held(held_rungs(n, k, held, m.n_experts), x.reshape(n, d), w,
              p["w_gate"], p["w_up"], p["w_down"], pos, order, sizes)
    if m.n_shared_experts:
        with jax.named_scope(obs.MOE_SHARED):
            y = y + mlp(p["shared"], x).reshape(n, d).astype(ACC)
    return y.reshape(b, t, d).astype(x.dtype), aux, sizes
