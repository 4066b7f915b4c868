"""Pallas TPU kernel for the chunked gated-linear-attention intra-chunk
block (Mamba2 SSD / RWKV6 shared core; see repro.models.ssm.gla_chunked).

One kernel invocation processes one (batch, head) pair for one chunk:
inputs q, k (L, K), v (L, V), cumulative log-decay lc (L, K or L, 1) and the
carried state S (K, V), all VMEM-resident; outputs y (L, V) and the updated
state. The pairwise decay matrix is built in registers from lc differences —
every exponent is ≤ 0 (overflow-safe, no FLA-style sub-chunking needed).

The host-side lax.scan over chunks lives in ops.gla_chunked_pallas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_kernel(q_ref, k_ref, v_ref, lc_ref, s_ref, y_ref, s_out_ref, *,
                  scalar_decay: bool, pre: bool, bonus_ref=None):
    q = q_ref[0, 0].astype(jnp.float32)          # (L, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)          # (L, V)
    lc = lc_ref[0, 0].astype(jnp.float32)        # (L, K) or (L, 1)
    s = s_ref[0, 0].astype(jnp.float32)          # (K, V)
    l = q.shape[0]

    lq = lc
    if pre:
        lq = jnp.concatenate([jnp.zeros_like(lc[:1]), lc[:-1]], axis=0)

    # inter-chunk
    q_eff = q * jnp.exp(lq)
    y = jax.lax.dot_general(q_eff, s, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # intra-chunk
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    mask = (ii > jj) if pre else (ii >= jj)
    if scalar_decay:
        ex = jnp.exp(jnp.where(mask, lq[:, 0][:, None] - lc[:, 0][None, :],
                               -jnp.inf))
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * ex
    else:
        # per-channel: factorized as sum_k (q ⊙ e^{lq})_ik (k ⊙ e^{-lc})_jk is
        # unsafe; build the masked pairwise tensor blockwise over K instead.
        def kslice(c0):
            e = jnp.exp(jnp.where(mask[:, :, None],
                                  lq[:, None, c0] - lc[None, :, c0],
                                  -jnp.inf))
            return jnp.einsum("ik,jk,ijk->ij", q[:, c0], k[:, c0], e)
        kdim = q.shape[1]
        csz = 16
        sc = sum(kslice(slice(c, min(c + csz, kdim)))
                 for c in range(0, kdim, csz))
    y = y + jax.lax.dot_general(sc, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    if pre and bonus_ref is not None:
        u = bonus_ref[0].astype(jnp.float32)     # (K,)
        y = y + ((q * u[None, :] * k).sum(axis=1, keepdims=True)) * v

    # state update
    k_eff = k * jnp.exp(lc[-1:] - lc)
    s_new = s * jnp.exp(lc[-1])[:, None] if not scalar_decay else \
        s * jnp.exp(lc[-1, 0])
    if scalar_decay:
        pass
    s_new = s_new + jax.lax.dot_general(
        k_eff, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    s_out_ref[0, 0] = s_new.astype(s_out_ref.dtype)


def gla_chunk_pallas(q, k, v, lc, state, *, pre=False, bonus=None,
                     interpret=False):
    """One chunk for all (B, H): q,k (B,H,L,K); v (B,H,L,V); lc (B,H,L,Kd);
    state (B,H,K,V). Returns y (B,H,L,V), new state."""
    b, h, l, kd = q.shape
    vd = v.shape[-1]
    scalar = lc.shape[-1] == 1

    kernel = functools.partial(_chunk_kernel, scalar_decay=scalar, pre=pre)
    in_specs = [
        pl.BlockSpec((1, 1, l, kd), lambda b_, h_: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, l, kd), lambda b_, h_: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, l, vd), lambda b_, h_: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, l, lc.shape[-1]), lambda b_, h_: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, kd, vd), lambda b_, h_: (b_, h_, 0, 0)),
    ]
    args = [q, k, v, lc, state]
    if pre and bonus is not None:
        kernel = functools.partial(_chunk_kernel, scalar_decay=scalar,
                                   pre=True)
        # bonus: (H, K) — passed as an extra ref
        def kernel_b(q_ref, k_ref, v_ref, lc_ref, s_ref, bon_ref, y_ref,
                     s_out_ref):
            _chunk_kernel(q_ref, k_ref, v_ref, lc_ref, s_ref, y_ref,
                          s_out_ref, scalar_decay=scalar, pre=True,
                          bonus_ref=bon_ref)
        kernel = kernel_b
        in_specs.append(pl.BlockSpec((1, kd), lambda b_, h_: (h_, 0)))
        args.append(bonus)

    y, s_new = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, l, vd), lambda b_, h_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, kd, vd), lambda b_, h_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, vd), v.dtype),
            jax.ShapeDtypeStruct((b, h, kd, vd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*args)
    return y, s_new
