"""Pytree optimizers (optax is not available offline; these are the
substrate implementations the trainer uses).

Each optimizer is an ``Optimizer(init, update)`` pair:
    state = init(params)
    new_params, new_state = update(params, grads, state, step)
All arithmetic is f32 regardless of param dtype (bf16-safe master math).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

PyTree = Any
F32 = jnp.float32


class Optimizer(NamedTuple):
    name: str
    init: Callable[[PyTree], PyTree]
    update: Callable[[PyTree, PyTree, PyTree, jax.Array], tuple]


def _cast_like(new, ref):
    return jax.tree.map(lambda n, r: n.astype(r.dtype), new, ref)


def sgd(lr: float, weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        return ()

    @jax.named_scope(obs.OPT)
    def update(params, grads, state, step):
        # routes through the fused local-step sweep: one blocked Pallas
        # pass over the flattened vector on TPU, the identical per-leaf
        # jnp update elsewhere (elementwise math — same bits either way)
        from repro.kernels.ops import fused_sgd
        return fused_sgd(params, grads, lr=lr, wd=weight_decay), state

    return Optimizer("sgd", init, update)


def momentum(lr: float, beta: float = 0.9,
             weight_decay: float = 0.0) -> Optimizer:
    """Heavy-ball momentum (DFedAvgM's local optimizer)."""
    def init(params):
        return {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)}

    @jax.named_scope(obs.OPT)
    def update(params, grads, state, step):
        def upd(p, g, m):
            g = g.astype(F32) + weight_decay * p.astype(F32)
            m = beta * m + g
            return (p.astype(F32) - lr * m).astype(p.dtype), m
        out = jax.tree.map(upd, params, grads, state["m"])
        new_p = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"m": new_m}

    return Optimizer("momentum", init, update)


def adam(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         weight_decay: float = 0.0, name: str = "adam") -> Optimizer:
    """Adam with L2 (coupled) weight decay — matches the paper's setup
    (Adam, weight decay 1e-4)."""
    def init(params):
        z = lambda p: jnp.zeros(p.shape, F32)
        return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params)}

    @jax.named_scope(obs.OPT)
    def update(params, grads, state, step):
        t = step.astype(F32) + 1.0
        c1 = 1.0 - b1 ** t
        c2 = 1.0 - b2 ** t

        def upd(p, g, m, v):
            g = g.astype(F32)
            if name == "adam" and weight_decay:
                g = g + weight_decay * p.astype(F32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / c1) / (jnp.sqrt(v / c2) + eps)
            pn = p.astype(F32) - lr * u
            if name == "adamw" and weight_decay:
                pn = pn - lr * weight_decay * p.astype(F32)
            return pn.astype(p.dtype), m, v

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {"m": pick(1), "v": pick(2)}

    return Optimizer(name, init, update)


def adamw(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.0) -> Optimizer:
    return adam(lr, b1, b2, eps, weight_decay, name="adamw")._replace(
        name="adamw")


def make_optimizer(name: str, lr: float, weight_decay: float = 0.0,
                   **kw) -> Optimizer:
    return {"sgd": sgd, "momentum": momentum, "adam": adam,
            "adamw": adamw}[name](lr, weight_decay=weight_decay, **kw)
