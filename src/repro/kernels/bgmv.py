"""Blocked BGMV kernel: batched low-rank corrections for factored serving.

Punica/S-LoRA-style multi-adapter serving observes that S models differing
only by rank-r deltas share one base GEMM: for member t with
W_t = W_base + U_t V_tᵀ,

    x @ W_t = x @ W_base + (x @ U_t) @ V_tᵀ

so the ensemble pays the M-byte base weight read ONCE per query batch and
each member only a rank-r "batched grouped matrix-vector" correction. This
kernel is that correction term for a whole `LowRankDeltaPool` member axis
in one grid:

    x (S, N, d_in) or (N, d_in) shared  ×  u (S, d_in, r), v (S, d_out, r)
      → (S, N, d_out) f32,   y_s = (x_s @ u_s) @ v_sᵀ

Grid is (S, N-blocks, d_out-blocks), d_out innermost. At the first
d_out block of each (member, N-block) the kernel computes the rank-r
projection t = x @ u from a (block_n, d_in) activation tile and the
member's (d_in, r) panel into a VMEM scratch; every d_out block then
writes one (block_n, block_o) output tile t @ v_blockᵀ. So x @ u runs
once per (member, N-block), not once per output tile, and the x and u
blocks are fetched once per (member, N-block) because their block index
does not move along the d_out axis. Tiling d_out is what lets the
tied-unembed site (d_out = vocabulary, 128256 for llama3.2-1b) compile:
with the whole d_out in one block its output window alone exceeds VMEM.
`block_n` shrinks with d_in so the double-buffered x tile stays inside a
fixed VMEM budget. The ragged N tail zero-pads to the block grid and is
sliced off, like every kernel in this package.

Shared-x form: when `x` has no member axis (the first layer of a factored
forward, before activations diverge per member), the x BlockSpec maps every
member row to the same tile — the activations are read once per member from
VMEM, never duplicated in HBM.

Routing follows `kernels/ops.py` discipline (DESIGN.md §5): Mosaic on TPU,
interpret mode for tests, and the pure-jnp twin (`kernels/ref.bgmv_ref`) as
the off-TPU production path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

BLOCK_N = 256            # activation rows per tile, before the d_in cap
BLOCK_O = 2048           # output columns per tile: (256, 2048) f32 = 2 MiB
X_TILE_BYTES = 2 << 20   # VMEM budget of one (block_n, d_in) x buffer
LANE = 128


def _out_block(d_out: int) -> int:
    """d_out tile: the whole axis when it fits in BLOCK_O, else the largest
    lane-aligned divisor of d_out up to BLOCK_O (no padding), else BLOCK_O
    with the axis zero-padded to a multiple of it."""
    if d_out <= BLOCK_O:
        return d_out
    for b in range(BLOCK_O, LANE - 1, -LANE):
        if d_out % b == 0:
            return b
    return BLOCK_O


def _row_block(n: int, d_in: int, itemsize: int, block_n: int) -> int:
    """N tile: at most `block_n`, capped so one x buffer stays within
    X_TILE_BYTES, a multiple of 16 rows (the bf16 sublane tile) or all of
    a smaller N."""
    cap = max(16, X_TILE_BYTES // (d_in * itemsize) // 16 * 16)
    b = min(block_n, cap)
    return b if n > b else max(n, 1)


def _bgmv_kernel(x_ref, ut_ref, vt_ref, out_ref, t_ref):
    """One (member, N-block, d_out-block) step, f32 accumulation.

    x_ref is (block_n, d_in) for shared x or (1, block_n, d_in) for
    per-member x — the reshape normalizes both layouts. The factor panels
    arrive transposed, (1, r, d_in) and (1, r, block_o)."""
    @pl.when(pl.program_id(2) == 0)
    def _project():
        x = x_ref[...].reshape(-1, x_ref.shape[-1]).astype(F32)
        t_ref[...] = jax.lax.dot_general(
            x, ut_ref[0].astype(F32), (((1,), (1,)), ((), ())),
            preferred_element_type=F32)                      # (bn, r)

    out_ref[0] = jax.lax.dot_general(
        t_ref[...], vt_ref[0].astype(F32), (((1,), (0,)), ((), ())),
        preferred_element_type=F32)                          # (bn, bo)


def bgmv_pallas(x, u, v, *, block_n: int = BLOCK_N, interpret: bool = False):
    """The blocked correction sweep. x: (S, N, d_in) per-member activations
    or (N, d_in) shared; u: (S, d_in, r); v: (S, d_out, r) → (S, N, d_out)
    f32. Oracle: `kernels.ref.bgmv_ref`."""
    s, d_in, r = u.shape
    d_out = v.shape[1]
    shared = x.ndim == 2
    n = x.shape[-2]
    assert x.shape == ((n, d_in) if shared else (s, n, d_in)), \
        (x.shape, u.shape)
    assert v.shape == (s, d_out, r), (v.shape, u.shape)
    block_n = _row_block(n, d_in, x.dtype.itemsize, block_n)
    pad = (-n) % block_n
    if pad:                       # ragged tail: zero rows, sliced off below
        width = ((0, pad), (0, 0)) if shared else ((0, 0), (0, pad), (0, 0))
        x = jnp.pad(x, width)
    block_o = _out_block(d_out)
    pad_o = (-d_out) % block_o
    if pad_o:                     # zero output columns, sliced off below
        v = jnp.pad(v, ((0, 0), (0, pad_o), (0, 0)))
    # r-minor factor panels would fill r of 128 lanes, and the compiler
    # relayouts such an operand into a lane-padded copy 128/r times its
    # size (328 MB for the tied unembed at S=5, r=8); transposed, d is the
    # lane axis and the copy is the factor's own size.
    ut, vt = jnp.swapaxes(u, 1, 2), jnp.swapaxes(v, 1, 2)

    if shared:
        x_spec = pl.BlockSpec((block_n, d_in), lambda i, j, k: (j, 0))
    else:
        x_spec = pl.BlockSpec((1, block_n, d_in), lambda i, j, k: (i, j, 0))
    out = pl.pallas_call(
        _bgmv_kernel,
        grid=(s, (n + pad) // block_n, (d_out + pad_o) // block_o),
        in_specs=[
            x_spec,
            pl.BlockSpec((1, r, d_in), lambda i, j, k: (i, 0, 0)),
            pl.BlockSpec((1, r, block_o), lambda i, j, k: (i, 0, k)),
        ],
        out_specs=pl.BlockSpec((1, block_n, block_o),
                               lambda i, j, k: (i, j, k)),
        out_shape=jax.ShapeDtypeStruct((s, n + pad, d_out + pad_o), F32),
        scratch_shapes=[pltpu.VMEM((block_n, r), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, ut, vt)
    return out[:, :n, :d_out] if pad or pad_o else out
