"""Pallas TPU flash attention (causal, GQA) — the TPU-target implementation
of repro.models.layers.flash_attention.

Tiling: grid (B, H, Tq/BQ, Tk/BK); the last grid axis accumulates the
online-softmax statistics in VMEM scratch (m, l, acc) and writes the output
tile once on the final KV block. Q/K/V tiles live in VMEM via BlockSpec; the
MXU sees (BQ, hd) x (hd, BK) and (BQ, BK) x (BK, hd) matmuls with
hardware-aligned 128-multiples by default.

GQA is expressed in the K/V index_map (kv head = h // group) — no
materialized head broadcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               bq: int, bk: int, causal: bool, window: int, scale: float,
               n_k: int, tk_valid: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale      # (BQ, hd)
    k = k_ref[0, :, 0, :].astype(jnp.float32)              # (BK, hd)
    v = v_ref[0, :, 0, :].astype(jnp.float32)              # (BK, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (BQ,BK)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < tk_valid
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0, :, 0, :] = (acc_ref[...] /
                             jnp.maximum(l_ref[...], 1e-30)
                             ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                           interpret=False):
    """q: (B, Tq, H, hd); k, v: (B, Tk, KV, hd) — Tq, Tk padded to blocks."""
    b, tq, h, hd = q.shape
    tk, n_kv = k.shape[1], k.shape[2]
    g = h // n_kv
    bq = min(bq, tq)
    bk = min(bk, tk)
    pq = (-tq) % bq
    pk = (-tk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    n_q, n_k = (tq + pq) // bq, (tk + pk) // bk

    kernel = functools.partial(
        _fa_kernel, bq=bq, bk=bk, causal=causal, window=window,
        scale=hd ** -0.5, n_k=n_k, tk_valid=tk)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b_, h_, q_, k_: (b_, q_, h_, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b_, h_, q_, k_: (b_, k_, h_ // g, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b_, h_, q_, k_: (b_, k_, h_ // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd),
                               lambda b_, h_, q_, k_: (b_, q_, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, tq + pq, h, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :tq]
