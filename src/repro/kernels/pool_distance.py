"""Pallas TPU kernel for the FedELMY pool-distance regularizers (Eq. 7–8).

The framework-level hot spot: computing dist(m, m_t) for every pool member
t means |M|+1 full sweeps over HBM if done naively (one per member, plus
one for d2). This kernel fuses them: one blocked pass over the flattened
parameter vector streams a (BP,) tile of the live model and the matching
(C, BP) tile of the *stacked* pool through VMEM and accumulates, per member,
the three sufficient statistics every supported measure needs:

    sq[t]  = Σ (w − m_t)²      (L2 / squared-L2)
    l1[t]  = Σ |w − m_t|       (L1)
    dot[t] = Σ w·m_t           (cosine, with norms[t] = Σ m_t²)

Arithmetic intensity is O(1) FLOP/byte — this is bandwidth-bound by design;
the win is the C-way fusion of HBM sweeps (napkin math in EXPERIMENTS.md
§Perf: pool C=6 → ~6× fewer HBM bytes than separate passes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_P = 65536          # 256 KiB f32 per member-row tile


def _pd_kernel_batched(w_ref, pool_ref, sq_ref, l1_ref, dot_ref, norm_ref, *,
                       n_blocks: int):
    # grid (B, n_blocks): the block index iterates fastest, so the (b, ·)
    # output tile is revisited across j and initialized at j == 0.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sq_ref[...] = jnp.zeros_like(sq_ref)
        l1_ref[...] = jnp.zeros_like(l1_ref)
        dot_ref[...] = jnp.zeros_like(dot_ref)
        norm_ref[...] = jnp.zeros_like(norm_ref)

    w = w_ref[...].astype(jnp.float32)          # (1, BP)       run b's tile
    m = pool_ref[0].astype(jnp.float32)         # (C, BP)       run b's pool
    r = w - m
    sq_ref[0] += jnp.sum(r * r, axis=1, keepdims=True)
    l1_ref[0] += jnp.sum(jnp.abs(r), axis=1, keepdims=True)
    dot_ref[0] += jnp.sum(w * m, axis=1, keepdims=True)
    norm_ref[0] += jnp.sum(m * m, axis=1, keepdims=True)


def pool_distance_stats(w_flat, pool_flat, *, block_p=BLOCK_P,
                        interpret=False):
    """Fused per-member statistics, single-run or batched:

    * w_flat (P,), pool_flat (C, P)        → stats each (C,)
    * w_flat (B, P), pool_flat (B, C, P)   → stats each (B, C) — B runs'
      pools in ONE blocked HBM sweep (grid (B, n_blocks)); `run_batch`'s
      experiment axis rides the leading grid dimension instead of paying B
      separate kernel launches. The single-run form is the B=1 slice of
      the same kernel.

    Returns dict of stats: sq, l1, dot, norm."""
    if w_flat.ndim == 1:
        stats = _pool_distance_stats_batched(
            w_flat[None], pool_flat[None], block_p=block_p,
            interpret=interpret)
        return {k: v[0] for k, v in stats.items()}
    return _pool_distance_stats_batched(w_flat, pool_flat, block_p=block_p,
                                        interpret=interpret)


def _pool_distance_stats_batched(w_flat, pool_flat, *, block_p=BLOCK_P,
                                 interpret=False):
    b, c, p = pool_flat.shape
    assert w_flat.shape == (b, p), (w_flat.shape, pool_flat.shape)
    pad = (-p) % block_p
    if pad:                       # ragged tail: zero-pad to the block grid
        w_flat = jnp.pad(w_flat, ((0, 0), (0, pad)))
        pool_flat = jnp.pad(pool_flat, ((0, 0), (0, 0), (0, pad)))
    n_blocks = (p + pad) // block_p

    kernel = functools.partial(_pd_kernel_batched, n_blocks=n_blocks)
    outs = pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((1, block_p), lambda i, j: (i, j)),
            pl.BlockSpec((1, c, block_p), lambda i, j: (i, 0, j)),
        ],
        out_specs=[pl.BlockSpec((1, c, 1), lambda i, j: (i, 0, 0))] * 4,
        out_shape=[jax.ShapeDtypeStruct((b, c, 1), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(w_flat, pool_flat)
    sq, l1, dot, norm = [o[:, :, 0] for o in outs]
    return {"sq": sq, "l1": l1, "dot": dot, "norm": norm}


# -- factor-form pool statistics (LowRankDeltaPool, DESIGN.md §13) ----------
#
# Pairwise member distances in factor form reduce to Gram matrices over the
# stacked factors: with rows A = [U_1ᵀ; …; U_Cᵀ] (C·r rows, d columns),
# ⟨Δ_i, Δ_j⟩ = ⟨U_iᵀU_j, V_iᵀV_j⟩_F reads off two A@Aᵀ products — r×r blocks
# of a (C·r)×(C·r) Gram — so ‖U_iV_iᵀ − U_jV_jᵀ‖² never materializes a
# d_in×d_out delta. The kernel below is that A@Aᵀ, blocked over the long
# parameter axis d like the stats sweep above; the M = C·r axis is tiny
# (pool capacity × rank), so the whole (M, M) accumulator tile stays
# resident in VMEM across the sweep.

BLOCK_P_GRAM = 2048      # (M, BP) f32 tile: M ≤ 256 → ≤ 2 MiB VMEM


def _gram_kernel(a_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[0].astype(jnp.float32)              # (M, BP)
    out_ref[0] += jax.lax.dot_general(
        a, a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def factor_gram(a, *, block_p=BLOCK_P_GRAM, interpret=False):
    """Blocked A @ Aᵀ over the trailing axis, f32 accumulation:

    * a (M, P)    → (M, M)
    * a (B, M, P) → (B, M, M) — B independent Grams (one per lead slice of
      a stacked transformer leaf) in one grid sweep.

    Oracle: `repro.kernels.ref.factor_gram_ref`."""
    if a.ndim == 2:
        return factor_gram(a[None], block_p=block_p, interpret=interpret)[0]
    b, m, p = a.shape
    pad = (-p) % block_p
    if pad:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)))
    n_blocks = (p + pad) // block_p
    return pl.pallas_call(
        _gram_kernel,
        grid=(b, n_blocks),
        in_specs=[pl.BlockSpec((1, m, block_p), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((1, m, m), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(a)


def distances_from_stats(stats, w_sq_norm, measure: str):
    """Per-member distances from fused stats. w_sq_norm = Σ w² — scalar for
    (C,) stats, (B,) for batched (B, C) stats."""
    if measure == "l2":
        return jnp.sqrt(stats["sq"] + 1e-12)
    if measure == "squared_l2":
        return stats["sq"]
    if measure == "l1":
        return stats["l1"]
    if measure == "cosine":
        w_sq = jnp.asarray(w_sq_norm)
        if stats["dot"].ndim == 2 and w_sq.ndim == 1:
            w_sq = w_sq[:, None]              # (B,) → (B, 1) vs (B, C)
        return 1.0 - stats["dot"] / (
            jnp.sqrt(w_sq + 1e-12) * jnp.sqrt(stats["norm"] + 1e-12))
    raise ValueError(measure)
