"""Jit'd public wrappers for the Pallas kernels.

The routing is a function of the backend alone. On TPU
(`jax.default_backend() == "tpu"`) every call site compiles the real
Mosaic kernels. Elsewhere the local-step ops and `bgmv` take their pure-jnp
twins (the production path off-TPU), and the remaining wrappers run their
kernels in interpret mode, the kernel body executing as jax ops. Oracles
for both routes live in `kernels/ref.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bgmv import bgmv_pallas
from repro.kernels.chunk_scan import gla_chunk_pallas
from repro.kernels.local_step import conv2d_gemm, maxpool2x2, sgd_update_tree
from repro.kernels.pool_distance import (distances_from_stats, factor_gram,
                                         pool_distance_stats)


@functools.cache
def _use_pallas() -> bool:
    """Real Mosaic kernels on TPU, the pure-jnp twins elsewhere —
    interpret-mode Pallas inside a training loop is strictly slower than
    XLA's fused jnp lowering, so off-TPU the jnp twin IS the production
    path. Resolved once per process: the backend cannot change after jax
    initializes."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret mode for the kernels that always run as Pallas: off-TPU
    only."""
    return not _use_pallas()


@functools.partial(jax.jit, static_argnames=("measure",))
def pool_distances(w_flat, pool_flat, *, measure="l2"):
    """Fused per-member distances (FedELMY d1/d2 hot path). Accepts either
    a single run — w (P,), pool (C, P) → (C,) — or a `run_batch` stack —
    w (B, P), pool (B, C, P) → (B, C) in one blocked sweep."""
    stats = pool_distance_stats(w_flat, pool_flat, interpret=_interpret())
    w_sq = jnp.sum(jnp.square(w_flat.astype(jnp.float32)), axis=-1)
    return distances_from_stats(stats, w_sq, measure)


@jax.jit
def factor_grams(a):
    """Blocked A @ Aᵀ ((…, M, P) → (…, M, M)) — the Gram building block of
    the factor-form pool statistics. Interpret mode off-TPU like every
    kernel wrapper."""
    return factor_gram(a, interpret=_interpret())


def lowrank_pool_sq(pool):
    """Pairwise ||m_i − m_j||² (C, C) of a `LowRankDeltaPool` through the
    blocked Gram kernel: the pool-diversity diagnostic at transformer
    scale, never materializing a d_in×d_out member delta."""
    from repro.core.distances import lowrank_pairwise_sq
    return lowrank_pairwise_sq(pool, gram_fn=factor_grams)


def tree_pool_distances(params, pool_members, *, measure="l2"):
    """Pytree front-end: flatten the live model and the stacked pool, then
    one fused kernel call. pool_members: stacked pytree (C leading)."""
    w = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                         for x in jax.tree.leaves(params)])
    pool = jnp.concatenate(
        [x.reshape(x.shape[0], -1).astype(jnp.float32)
         for x in jax.tree.leaves(pool_members)], axis=1)
    return pool_distances(w, pool, measure=measure)


@functools.partial(jax.jit, static_argnames=("chunk", "pre"))
def gla_chunked(q, k, v, log_decay, *, chunk: int, pre=False, bonus=None,
                initial_state=None):
    """Chunked GLA via the Pallas intra-chunk kernel, host scan over chunks.
    Layouts match repro.models.ssm.gla_chunked: q,k (B,T,H,K); v (B,T,H,V);
    log_decay (B,T,H[,K])."""
    b, t, h, kd = q.shape
    vd = v.shape[-1]
    if log_decay.ndim == 3:
        log_decay = log_decay[..., None]
    assert t % chunk == 0
    nc = t // chunk

    def r(x):  # (B,T,H,*) -> (NC, B, H, L, *)
        return x.reshape(b, nc, chunk, h, x.shape[-1]).transpose(1, 0, 3, 2, 4)

    qc, kc, vc, ldc = r(q), r(k), r(v), r(log_decay)
    state = (jnp.zeros((b, h, kd, vd), jnp.float32) if initial_state is None
             else initial_state)

    def step(S, xs):
        qx, kx, vx, ld = xs
        lc = jnp.cumsum(ld.astype(jnp.float32), axis=2)
        y, S = gla_chunk_pallas(qx, kx, vx, lc, S, pre=pre, bonus=bonus,
                                interpret=_interpret())
        return S, y

    S, ys = jax.lax.scan(step, state, (qc, kc, vc, ldc))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(b, t, h, vd)
    return y, S


# ---------------------------------------------------------------------------
# Fused local-step ops (kernels/local_step.py): the conv CNN's scan-safe
# hot path. No jit wrappers here — these are always called from inside the
# trainer's compiled step programs (or a jitted eval), never eagerly.
# ---------------------------------------------------------------------------

def bgmv(x, u, v):
    """Batched low-rank serving correction y_s = (x_s @ u_s) @ v_tᵀ over the
    pool-member axis (`kernels/bgmv.py`, DESIGN.md §14) — the per-member
    term of the factored ensemble forward `x@W_t = x@W_base + (x@U_t)@V_tᵀ`.
    x: (S, N, d_in) or shared (N, d_in); u (S, d_in, r); v (S, d_out, r) →
    (S, N, d_out) f32. Called from inside the server's compiled scoring
    programs, so no jit wrapper; Pallas on TPU, the `ref.bgmv_ref` jnp twin
    elsewhere (interpret-mode Pallas in a scoring loop is strictly slower
    than XLA's fused lowering, same routing as the local-step ops)."""
    if _use_pallas():
        return bgmv_pallas(x, u, v)
    from repro.kernels.ref import bgmv_ref
    return bgmv_ref(x, u, v)


# ---------------------------------------------------------------------------
# Grouped matmul of the held experts (models/moe.py)
# ---------------------------------------------------------------------------

GMM_TM = 512              # rows of a tile; the rows are padded to it


def _gmm_tile(dim: int) -> int:
    """A contraction or output tile: the whole dim up to 1536, else 512
    (a v5e's scoped VMEM holds the f32 accumulator and the double-buffered
    blocks of a (512, 512, 1408) tile)."""
    return dim if dim <= 1536 else 512


def _gmm_tiling(m: int, k: int, n: int):
    return (min(GMM_TM, m), _gmm_tile(k), _gmm_tile(n))


def grouped_matmul_ref(x, w, sizes, out_dtype):
    """The jnp twin of `grouped_matmul`: each group's product masked to its
    rows, summed over the groups (G x the work; the CPU path and the
    oracle)."""
    ends = jnp.cumsum(sizes)
    row = jnp.arange(x.shape[0])
    member = (row[:, None] >= ends[None, :] - sizes[None, :]) & \
        (row[:, None] < ends[None, :])                      # (M, G)
    y = jnp.einsum("md,gdf,mg->mf", x, w, member.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def grouped_matmul(x, w, sizes, out_dtype=jnp.float32):
    """Rows of x (M, d) in consecutive groups of `sizes` (G,) rows, each
    times its group's w[g] (G, d, f) -> (M, f); rows past sum(sizes) are
    zero. On TPU the megablox kernel, which visits only the tiles that
    hold a group's rows, so its work scales with sum(sizes) and not with
    M; elsewhere `grouped_matmul_ref`. Differentiable in x and w."""
    if not _use_pallas():
        return grouped_matmul_ref(x, w, sizes, out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m = x.shape[0]
    tm = min(GMM_TM, m)
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    # One trailing group holds the rows past the held experts' (padding
    # included); with G weight panels the kernel leaves it out.
    rest = (m + pad - jnp.sum(sizes))[None].astype(jnp.int32)
    y = gmm(x, w, jnp.concatenate([sizes.astype(jnp.int32), rest]),
            out_dtype, _gmm_tiling)
    return y[:m] if pad else y


# ---------------------------------------------------------------------------
# Self-attention of a whole sequence (models/layers.flash_attention)
# ---------------------------------------------------------------------------

# Query and key block sizes, largest first: a call takes the first that
# divides its sequence.
ATTN_BLOCKS = (512, 256, 128)
# `jax_default_matmul_precision` values under which the jnp scan's f32
# products are single bf16 passes, as the kernel's are.
_ONE_PASS = (None, "default", "bfloat16")


def attention_fits(q, k, v, *, window: int, q_offset: int) -> bool:
    """Whether `attention` takes a `layers.flash_attention` call: on TPU,
    a whole sequence attending to itself (q_offset 0, Tq == Tk, a multiple
    of the smallest block) under no window shorter than it, with bf16 q, k
    and v, where the configuration leaves products at one bf16 pass."""
    t = k.shape[1]
    return (_use_pallas() and q_offset == 0 and q.shape[1] == t
            and t % ATTN_BLOCKS[-1] == 0 and (not window or window >= t)
            and all(x.dtype == jnp.bfloat16 for x in (q, k, v))
            and jax.config.jax_default_matmul_precision in _ONE_PASS)


@functools.cache
def _splash_lib():
    """jax's splash attention package, its kernels' custom calls carrying
    no kernel metadata.

    The splash kernels hand `pallas_call` their block sizes as profiler
    metadata, which the compiled program prints as a multi-line attribute
    ahead of the op's `metadata={op_name=...}`: the instruction then spans
    three lines of HLO text and a line-wise reader of the compiled step
    (`bench/scopes.py`) finds the kernels under no `obs` scope. The kernel
    module's `pallas_call` is replaced, once per process, by one that
    drops that metadata; the kernels, their grid and their
    `backend_config` are unchanged."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    from jax.experimental.pallas.ops.tpu.splash_attention import \
        splash_attention_kernel as kernel_module

    class _Pallas:
        def __getattr__(self, name):
            return getattr(pl, name)

        @staticmethod
        def pallas_call(*args, metadata=None, **kwargs):
            return pl.pallas_call(*args, **kwargs)

    kernel_module.pl = _Pallas()
    return sa


@functools.cache
def _splash(heads: int, seq: int, causal: bool, interpret: bool):
    """The splash kernel for `heads` query heads over `seq` tokens, its
    block mask and block sizes built once per shape."""
    sa = _splash_lib()
    b = next(b for b in ATTN_BLOCKS if seq % b == 0)
    blocks = sa.BlockSizes(block_q=b, block_kv=b, block_q_dkv=b,
                           block_kv_dkv=b, block_q_dq=b, block_kv_dq=b)
    mask = sa.CausalMask((seq, seq)) if causal else sa.FullMask((seq, seq))
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mha_single_device(
            sa.MultiHeadMask((mask,) * heads), block_sizes=blocks,
            interpret=interpret)


def attention(q, k, v, *, causal=True, scale=None):
    """softmax(scale q kᵀ) v over one sequence, by jax's splash attention
    kernels, forward and backward: a causal call visits only the blocks on
    or below the diagonal, and the backward keeps the forward's output and
    log-sum-exp, not a residual per block. q (B, T, H, hd); k (B, T, KV,
    hd) and v (B, T, KV, vd) with H a multiple of KV (grouped queries read
    their KV head, never a repeated copy); scale None is hd^-1/2. q is
    scaled in f32 and rounded to its dtype; products take bf16 operands
    into f32, and the probabilities meet v in f32. Returns (B, T, H, vd)
    in q's dtype. Called from inside compiled programs, so no jit wrapper;
    interpret mode off-TPU (the tests' route: `layers.flash_attention`
    takes this only where `attention_fits`)."""
    b, t, h, hd = q.shape
    scale = hd ** -0.5 if scale is None else scale
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    kernel = _splash(h, t, causal, _interpret())

    def heads_first(x):             # (B, T, N, d) <-> (B, N, T, d)
        return x.transpose(0, 2, 1, 3)

    o = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(o)


def fused_conv2d(x, w, b):
    """SAME stride-1 NHWC conv as im2col + blocked GEMM — forward and
    backward contain no `lax.conv`, so the op is scan-safe (no conv-in-scan
    cliff, DESIGN.md §9) and vmaps over per-run weights as a batched
    matmul (no grouped-conv fallback, DESIGN.md §6). Pallas kernel on TPU,
    jnp GEMM twin elsewhere."""
    return conv2d_gemm(x, w, b, use_pallas=_use_pallas())


def fused_maxpool2x2(x):
    """Scan-safe non-overlapping 2×2 max pool (reshape + max; the VJP is
    mask arithmetic, not select-and-scatter)."""
    return maxpool2x2(x)


def fused_sgd(params, grads, *, lr, wd=0.0):
    """SGD update p ← p − lr·(g + wd·p) with f32 master math. On TPU the
    flattened parameter vector goes through ONE blocked Pallas sweep
    (`local_step.sgd_update_flat`); elsewhere the per-leaf jnp update runs
    directly — the math is elementwise, so both routes are bit-identical
    to `optimizers.sgd`'s update rule."""
    return sgd_update_tree(params, grads, lr=lr, wd=wd,
                           use_pallas=_use_pallas())
