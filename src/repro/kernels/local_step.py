"""Fused local-step kernels: the conv CNN's hot path without `lax.conv`.

DESIGN.md §9 documents the cliff this module removes: XLA CPU lowers
`lax.conv_general_dilated` *inside* a `lax.scan` body ~20× slower than the
dispatched conv thunks, which locked the paper CNN — the model behind the
headline CIFAR-10 claim — out of the scan-compiled local phase behind a
`DataPlan(scan=False)` carve-out. The same lowering is why vmapped
per-run-weight convs (the `run_batch` axis) fell to slow grouped convs
(DESIGN.md §6, table1 `batch_speedup=0.95`).

The fix is a change of formulation, not a tweak of the loop: express the
conv as im2col + GEMM so the scan body contains only pad/slice/matmul —
primitives XLA scans and vmaps well on every backend — and give the GEMM a
blocked Pallas kernel for TPU. Three layers:

* `im2col` — SAME stride-1 patch extraction via pad + `lax.slice` + concat.
  Deliberately NOT `lax.conv_general_dilated_patches`: its VJP is itself a
  conv, which would re-introduce the cliff through the backward pass.
  Slice/pad transpose to pad/slice-add, so fwd AND bwd stay scan-safe.
* `matmul_blocked` — a Pallas blocked matmul reusing `pool_distance.py`'s
  accumulation pattern: the reduction block index iterates fastest, the
  output tile is revisited across K blocks and zero-initialized at k == 0;
  ragged dims zero-pad to the block grid (zeros are additive identity for
  the accumulation, so padding never leaks). `pallas_call` has no autodiff,
  so the Pallas route wraps it in a `custom_vjp` whose backward runs the
  SAME blocked kernel (dA = G·Bᵀ, dB = Aᵀ·G) — conv forward and backward
  both ride the kernel.
* `sgd_update_flat` — the SGD half of the fused step: p ← p − lr·(g + wd·p)
  over the flattened parameter vector as one blocked HBM sweep (f32 master
  math, bit-identical to `optim.optimizers.sgd`'s per-leaf update).

Routing follows `kernels/ops.py` discipline: the public wrappers there pick
`use_pallas=True` on TPU and the pure-jnp twin elsewhere — interpret-mode
Pallas in a training loop is strictly slower than XLA's fused jnp lowering,
so off-TPU the jnp branch IS the production path (ROADMAP item 2's
"fall back to ref.py jnp paths off-TPU"). Oracles live in `kernels/ref.py`;
`tests/test_local_step.py` pins both branches against them.

`fused_loss_for` is the per-model capability probe the trainer consults:
models that can't scan their native loss (the conv CNN) attach a
GEMM-formulated twin under `FUSED_LOSS_ATTR`; matmul models probe to
themselves and keep their current step bodies unchanged.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

BLOCK_M = 128            # f32 MXU-friendly tiles (min tile 8×128)
BLOCK_N = 128
BLOCK_K = 128
BLOCK_P = 65536          # flat-vector sweep tile, matches pool_distance.py

# Attribute under which a model registers its scan-safe loss twin — the
# capability `fused_loss_for` probes (see module docstring).
FUSED_LOSS_ATTR = "fused_step_loss"


def fused_loss_for(loss_fn: Callable) -> Callable:
    """Per-model capability probe: the loss the compiled steps should be
    built over. Conv models (`models/cnn.py`) attach their im2col + GEMM
    twin under ``FUSED_LOSS_ATTR`` — grads and updates then contain no
    `lax.conv`, so the scanned/vmapped step bodies avoid the conv-in-scan
    and grouped-conv lowerings. Models without the attribute (every matmul
    model) resolve to themselves: their step bodies are unchanged."""
    return getattr(loss_fn, FUSED_LOSS_ATTR, None) or loss_fn


# ---------------------------------------------------------------------------
# im2col: scan-safe patch extraction
# ---------------------------------------------------------------------------

def im2col(x: jax.Array, k: int = 3) -> jax.Array:
    """(B, H, W, C) → (B, H, W, k·k·C) SAME stride-1 patches, ordered
    (kh, kw, c) to match a (kh, kw, C_in, C_out) filter's reshape to
    (kh·kw·C_in, C_out). Pure pad + slice + concat — see module docstring
    for why this is NOT `conv_general_dilated_patches`."""
    b, h, w, c = x.shape
    lo = (k - 1) // 2
    hi = k - 1 - lo
    xp = jnp.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0)))
    cols = [jax.lax.slice(xp, (0, i, j, 0), (b, i + h, j + w, c))
            for i in range(k) for j in range(k)]
    return jnp.concatenate(cols, axis=-1)


# ---------------------------------------------------------------------------
# Blocked matmul kernel (pool_distance.py's accumulation pattern on the
# GEMM reduction axis)
# ---------------------------------------------------------------------------

def _mm_kernel(a_ref, b_ref, o_ref):
    # grid (M/bm, N/bn, K/bk): the K block index iterates fastest, so the
    # (i, j) output tile is revisited across k and initialized at k == 0.
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...].astype(F32), b_ref[...].astype(F32),
                          preferred_element_type=F32)


def matmul_blocked(a: jax.Array, b: jax.Array, *, block_m: int = BLOCK_M,
                   block_n: int = BLOCK_N, block_k: int = BLOCK_K,
                   interpret: bool = False) -> jax.Array:
    """(M, K) @ (K, N) → (M, N) f32 through VMEM-sized tiles. Ragged dims
    zero-pad to the block grid; the pad rows/cols contribute zeros to the
    accumulation and are sliced off the result."""
    m, kd = a.shape
    kd2, n = b.shape
    assert kd == kd2, (a.shape, b.shape)
    pm, pk, pn = (-m) % block_m, (-kd) % block_k, (-n) % block_n
    ap = jnp.pad(a, ((0, pm), (0, pk))) if pm or pk else a
    bp = jnp.pad(b, ((0, pk), (0, pn))) if pk or pn else b
    grid = ((m + pm) // block_m, (n + pn) // block_n, (kd + pk) // block_k)
    out = pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
                  pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]


def _make_gemm_pallas(interpret: bool):
    """Pallas GEMM with a custom VJP that routes the backward through the
    same blocked kernel (pallas_call itself has no autodiff rule)."""

    @jax.custom_vjp
    def gemm_pallas(a, b):
        return matmul_blocked(a, b, interpret=interpret)

    def fwd(a, b):
        return matmul_blocked(a, b, interpret=interpret), (a, b)

    def bwd(res, g):
        a, b = res
        da = matmul_blocked(g, b.T, interpret=interpret)
        db = matmul_blocked(a.T, g, interpret=interpret)
        return da.astype(a.dtype), db.astype(b.dtype)

    gemm_pallas.defvjp(fwd, bwd)
    return gemm_pallas


_GEMM_PALLAS = {False: _make_gemm_pallas(False), True: _make_gemm_pallas(True)}


def gemm(a: jax.Array, b: jax.Array, *, use_pallas: bool = False,
         interpret: bool = False) -> jax.Array:
    """f32 matmul: the blocked Pallas kernel when ``use_pallas`` (its VJP
    runs the same kernel), else the jnp twin XLA fuses natively — the
    production path off-TPU, scan- and vmap-safe either way."""
    if use_pallas:
        return _GEMM_PALLAS[bool(interpret)](a, b)
    return jnp.dot(a.astype(F32), b.astype(F32))


# ---------------------------------------------------------------------------
# Conv + pooling in GEMM form
# ---------------------------------------------------------------------------

def conv2d_gemm(x: jax.Array, w: jax.Array, b: jax.Array, *,
                use_pallas: bool = False,
                interpret: bool = False) -> jax.Array:
    """SAME stride-1 NHWC conv as im2col + blocked matmul: forward and
    backward lower to pad/slice/GEMM only — no `lax.conv` on any backend,
    so the op scans (no conv-in-scan cliff) and vmaps over per-run weights
    (batched matmul, not grouped convs). w: (kh, kw, C_in, C_out)."""
    k = w.shape[0]
    cols = im2col(x, k)
    bsz, h, wd, kk = cols.shape
    y = gemm(cols.reshape(-1, kk), w.reshape(kk, -1),
             use_pallas=use_pallas, interpret=interpret)
    return y.reshape(bsz, h, wd, -1) + b


def maxpool2x2(x: jax.Array) -> jax.Array:
    """Non-overlapping 2×2 max pool as reshape + max — forward-identical to
    `lax.reduce_window`, but its VJP is mask arithmetic instead of
    select-and-scatter, which keeps the backward scan-safe. (Gradient
    tie-breaking differs from select-and-scatter; the engine uses ONE
    formulation on every step path, so the bit-identity contracts are
    unaffected.)"""
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


# ---------------------------------------------------------------------------
# Fused SGD update sweep
# ---------------------------------------------------------------------------

def _sgd_kernel(p_ref, g_ref, o_ref, *, lr: float, wd: float):
    p = p_ref[...].astype(F32)
    g = g_ref[...].astype(F32) + wd * p
    o_ref[...] = p - lr * g


def sgd_update_flat(p_flat: jax.Array, g_flat: jax.Array, *, lr: float,
                    wd: float = 0.0, block_p: int = BLOCK_P,
                    interpret: bool = False) -> jax.Array:
    """p ← p − lr·(g + wd·p) over a flat (P,) vector as one blocked HBM
    sweep — bit-identical to the per-leaf `optimizers.sgd` math (the update
    is elementwise, so flattening cannot reassociate anything). Ragged
    tails zero-pad; pad lanes compute 0 − lr·0 and are sliced off."""
    (p,) = p_flat.shape
    assert g_flat.shape == (p,), (p_flat.shape, g_flat.shape)
    pad = (-p) % block_p
    pp = jnp.pad(p_flat, (0, pad)) if pad else p_flat
    gp = jnp.pad(g_flat, (0, pad)) if pad else g_flat
    n_blocks = (p + pad) // block_p
    out = pl.pallas_call(
        functools.partial(_sgd_kernel, lr=lr, wd=wd),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, block_p), lambda i: (0, i))] * 2,
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p + pad), F32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pp[None], gp[None])
    return out[0, :p].astype(p_flat.dtype)


def sgd_update_tree(params, grads, *, lr: float, wd: float = 0.0,
                    use_pallas: bool = False, interpret: bool = False):
    """Pytree front-end for the fused SGD sweep: flatten-concat the leaves,
    one kernel pass, split back. Off the Pallas route it applies the
    per-leaf jnp update directly (same elementwise ops, same bits, no
    concat copies) — the production path off-TPU."""
    if not use_pallas:
        def upd(p, g):
            g32 = g.astype(F32) + wd * p.astype(F32)
            return (p.astype(F32) - lr * g32).astype(p.dtype)
        return jax.tree.map(upd, params, grads)
    leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    flat = jnp.concatenate([x.reshape(-1).astype(F32) for x in leaves])
    g_flat = jnp.concatenate([g.reshape(-1).astype(F32) for g in g_leaves])
    new_flat = sgd_update_flat(flat, g_flat, lr=lr, wd=wd,
                               interpret=interpret)
    out, off = [], 0
    for x in leaves:
        n = x.size
        out.append(new_flat[off:off + n].reshape(x.shape).astype(x.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)
