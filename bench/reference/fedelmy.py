"""Plain reference of one FedELMY client visit (paper Alg. 1, lines 3-17)
as the timed path runs it, over any model's reference loss.

A visit: `e_warmup` Adam steps on the task loss (the chain's first client
only), then S pool slots. Slot j starts from the pool's average (Eq. 6),
takes `e_local` Adam steps on

    task - alpha * d1 / s1 + beta * d2 / s2        (Eq. 9)

where d1 is the mean L2 distance to the live pool members (Eq. 7), d2 the
L2 distance to member 0 (Eq. 8), and s1, s2 the appendix's calibration
10^(floor(log10 d) + 1 - floor(log10 task)), held constant under the
gradient; then the slot's model joins the pool. Every phase starts Adam
afresh (bias-corrected, L2 weight decay added to the gradient, f32 moment
and update arithmetic, the result stored in the parameter's dtype).

Two pool forms, as the configuration states: "stacked" keeps every member
whole; "lowrank" keeps member t as base + U_t V_t^T for each leaf whose
last two axes are both at least 8 (a dense delta for the rest), where
U_t = qr(delta @ Omega) and V_t = delta^T U_t with a fixed Gaussian Omega
per leaf, drawn from key 20240412 folded with the leaf's index: the
projection is part of the pool's definition, not a draw of the run.

Either pool holds S + 1 slots from the start, the unfilled ones masked
out of every mean, so that one compiled slot program serves every slot.
The client's data is an argument of each program, never a constant in it.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
OMEGA_SEED = 20240412
FACTOR_MIN = 8


def schedule(seed: int, n_rows: int, batch: int, n_steps: int) -> np.ndarray:
    """Index rows a client stream serves: whole epochs, each a fresh
    permutation from numpy's generator seeded with the stream's seed,
    cut to full batches."""
    rng = np.random.default_rng(seed)
    per_epoch = n_rows // batch
    rows = []
    while len(rows) < n_steps:
        perm = rng.permutation(n_rows)
        rows.extend(perm[:per_epoch * batch].reshape(per_epoch, batch))
    return np.stack(rows[:n_steps]).astype(np.int32)


def _calibrated(d, task):
    mag_d = jnp.floor(jnp.log10(jnp.maximum(jax.lax.stop_gradient(d), 1e-12)))
    mag_l = jnp.floor(jnp.log10(jnp.maximum(jax.lax.stop_gradient(task),
                                            1e-12)))
    return d / jnp.maximum(10.0 ** (mag_d + 1.0 - mag_l), 1e-12)


def _adam(hp):
    lr, wd = hp["learning_rate"], hp["weight_decay"]
    b1, b2, eps = 0.9, 0.999, 1e-8

    def update(p, g, m, v, t):
        g = g.astype(F32) + wd * p.astype(F32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return (p.astype(F32) - lr * u).astype(p.dtype), m, v

    def apply(params, grads, m, v, step):
        t = step.astype(F32) + 1.0
        out = jax.tree.map(lambda p, g, a, b: update(p, g, a, b, t),
                           params, grads, m, v)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    return apply


# -- pools: (state, count) with S + 1 slots, member 0 the visit's start -----

def _factored(shape) -> bool:
    return len(shape) >= 2 and min(shape[-2:]) >= FACTOR_MIN


def _live(count, capacity):
    return (jnp.arange(capacity) < count).astype(F32)


class Stacked:
    """Every member whole, in the parameters' dtype."""

    @staticmethod
    def create(base, capacity, hp):
        return jax.tree.map(
            lambda b: jnp.zeros((capacity,) + b.shape, b.dtype).at[0].set(b),
            base)

    @staticmethod
    def capacity(state):
        return jax.tree.leaves(state)[0].shape[0]

    @staticmethod
    def average(state, count):
        w = _live(count, Stacked.capacity(state)) / count.astype(F32)
        return jax.tree.map(
            lambda s: jnp.einsum("c,c...->...", w, s.astype(F32),
                                 precision=HI).astype(s.dtype), state)

    @staticmethod
    def append(state, count, params, hp):
        return jax.tree.map(lambda s, p: s.at[count].set(p.astype(s.dtype)),
                            state, params)

    @staticmethod
    def member_sq(params, state):
        """(C,) squared distances to every slot (dead slots included)."""
        return sum(
            jnp.sum(jnp.square(p.astype(F32)[None] - s.astype(F32)),
                    axis=tuple(range(1, s.ndim)))
            for p, s in zip(jax.tree.leaves(params), jax.tree.leaves(state)))


class LowRank:
    """base + truncated rank-r deltas (see the module docstring). State:
    (base, [per leaf: (u (C,...,d_in,r), v (C,...,d_out,r)) | dense (C,...)])."""

    @staticmethod
    def create(base, capacity, hp):
        deltas = []
        for b in jax.tree.leaves(base):
            if _factored(b.shape):
                r = min(hp["pool_rank"], b.shape[-2], b.shape[-1])
                deltas.append((
                    jnp.zeros((capacity,) + b.shape[:-1] + (r,), F32),
                    jnp.zeros((capacity,) + b.shape[:-2] + (b.shape[-1], r),
                              F32)))
            else:
                deltas.append(jnp.zeros((capacity,) + b.shape, F32))
        return (base, deltas)

    @staticmethod
    def capacity(state):
        d = state[1][0]
        return (d[0] if isinstance(d, tuple) else d).shape[0]

    @staticmethod
    def average(state, count):
        base, deltas = state
        w = _live(count, LowRank.capacity(state)) / count.astype(F32)
        out = []
        for b, d in zip(jax.tree.leaves(base), deltas):
            if isinstance(d, tuple):
                acc = jnp.einsum("c,c...ir,c...jr->...ij", w, d[0], d[1],
                                 precision=HI)
            else:
                acc = jnp.einsum("c,c...->...", w, d, precision=HI)
            out.append((b.astype(F32) + acc).astype(b.dtype))
        return jax.tree.unflatten(jax.tree.structure(base), out)

    @staticmethod
    def append(state, count, params, hp):
        base, deltas = state
        out = []
        for i, (b, p, d) in enumerate(zip(jax.tree.leaves(base),
                                          jax.tree.leaves(params), deltas)):
            delta = p.astype(F32) - b.astype(F32)
            if not isinstance(d, tuple):
                out.append(d.at[count].set(delta))
                continue
            r = d[0].shape[-1]
            omega = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(OMEGA_SEED), i),
                (delta.shape[-1], r), F32)
            y = jnp.einsum("...io,or->...ir", delta, omega, precision=HI)
            q, _ = jnp.linalg.qr(y)
            v = jnp.einsum("...io,...ir->...or", delta, q, precision=HI)
            out.append((d[0].at[count].set(q), d[1].at[count].set(v)))
        return (base, out)

    @staticmethod
    def member(state, t):
        """Member t in f32: base + U_t V_t^T (the dense delta elsewhere)."""
        base, deltas = state
        out = []
        for b, d in zip(jax.tree.leaves(base), deltas):
            acc = (jnp.einsum("...ir,...jr->...ij", d[0][t], d[1][t],
                              precision=HI) if isinstance(d, tuple) else d[t])
            out.append(b.astype(F32) + acc)
        return jax.tree.unflatten(jax.tree.structure(base), out)

    @staticmethod
    def member_sq(params, state):
        """||m - m_t||^2 for every slot t (slot 0 is the base, unfilled
        slots read as the base too), from ||G||^2 - 2<G^T U, V> +
        <U^T U, V^T V> with G = m - base."""
        base, deltas = state
        total = 0.0
        for p, b, d in zip(jax.tree.leaves(params), jax.tree.leaves(base),
                           deltas):
            g = p.astype(F32) - b.astype(F32)
            if isinstance(d, tuple):
                u, v = d
                axes = tuple(range(1, u.ndim))
                gu = jnp.einsum("...io,c...ir->c...or", g, u, precision=HI)
                uu = jnp.einsum("c...ir,c...is->c...rs", u, u, precision=HI)
                vv = jnp.einsum("c...ir,c...is->c...rs", v, v, precision=HI)
                total = total + jnp.sum(g * g) - 2.0 * jnp.sum(
                    gu * v, axis=axes) + jnp.sum(uu * vv, axis=axes)
            else:
                total = total + jnp.sum(jnp.square(g[None] - d),
                                        axis=tuple(range(1, d.ndim)))
        return jnp.maximum(total, 0.0)


POOLS = {"stacked": Stacked, "lowrank": LowRank}


# -- the visit ---------------------------------------------------------------

def visit(loss: Callable, params, data: Dict[str, Any], rows: np.ndarray,
          hp: Dict[str, Any], *, warmup: bool) -> Dict[str, Any]:
    """One client visit over `rows` (its index rows, in order). Returns the
    last task loss of each slot, the handed-off params (the pool average),
    and each leaf's first gradient norm."""
    adam = _adam(hp)
    alpha, beta = hp["alpha"], hp["beta"]
    pool = POOLS[hp["pool"]]
    capacity = hp["pool_size"] + 1

    def zeros(p):
        return jax.tree.map(lambda x: jnp.zeros(x.shape, F32), p)

    def steps(grad_fn, params, data, rows):
        def body(c, xs):
            p, m, v = c
            s, row = xs
            (task, norms), g = grad_fn(p, {k: a[row] for k, a in data.items()})
            p, m, v = adam(p, g, m, v, s)
            return (p, m, v), (task, norms)
        (p, _, _), out = jax.lax.scan(
            body, (params, zeros(params), zeros(params)),
            (jnp.arange(rows.shape[0]), rows))
        return p, out

    @jax.jit
    def warm(params, data, rows):
        def grad_fn(p, batch):
            task, g = jax.value_and_grad(loss)(p, batch)
            return (task, jax.tree.map(
                lambda x: jnp.linalg.norm(x.astype(F32).ravel()), g)), g
        p, (_, norms) = steps(grad_fn, params, data, rows)
        return p, jax.tree.map(lambda n: n[0], norms)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def slot(state, count, data, rows):
        live = _live(count, capacity)

        def full(p, batch):
            task = loss(p, batch)
            d = jnp.sqrt(pool.member_sq(p, state) + 1e-12)
            d1 = jnp.sum(d * live) / count.astype(F32)
            total = task - alpha * _calibrated(d1, task) \
                + beta * _calibrated(d[0], task)
            return total, task

        def grad_fn(p, batch):
            (_, task), g = jax.value_and_grad(full, has_aux=True)(p, batch)
            return (task, ()), g
        p, (tasks, _) = steps(grad_fn, pool.average(state, count), data, rows)
        return pool.append(state, count, p, hp), tasks[-1]

    rows = jnp.asarray(rows)
    first_norms = None
    if warmup:
        params, first_norms = warm(params, data, rows[:hp["e_warmup"]])
        rows = rows[hp["e_warmup"]:]
    state = pool.create(params, capacity, hp)
    losses = []
    e = hp["e_local"]
    for j in range(hp["pool_size"]):
        state, task = slot(state, jnp.int32(j + 1), data,
                           rows[j * e:(j + 1) * e])
        losses.append(float(task))
    handoff = jax.jit(pool.average)(state, jnp.int32(capacity))
    return {"slot_losses": losses, "handoff": handoff,
            "first_grad_norms": first_norms}
