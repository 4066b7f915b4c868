"""The FedELMY model pool (paper §3.2).

Three representations:

* ``ModelPool`` — paper-faithful: the pool is a stacked pytree with a fixed
  capacity (S+1) and a member count; every member's full parameters are kept
  (cost (S+1)·M). Averaging (Eq. 5/6) is a masked mean over the stack axis —
  collective-free under pjit because members share one sharding.

* ``MomentPool`` — beyond-paper memory-efficient form: keeps only the
  running member mean μ, the member count n, and the scalar mean of squared
  member norms q = (1/n)Σ_t ||w_t||². This supports the squared-L2 diversity
  regularizer exactly:

      mean_t ||w − w_t||² = ||w||² − 2⟨w, μ⟩ + q

  shrinking pool memory from (S+1)·M to M + O(1) (enables 70B-scale pools;
  see DESIGN.md §3 and EXPERIMENTS.md §Perf).

* ``LowRankDeltaPool`` — LoRA-style factor form for transformer-scale
  clients: member t is ``base + U_t @ V_tᵀ`` per matrix leaf (plus small
  dense deltas for vectors/norms), so pool memory is M + (S+1)·r·(d_in+d_out)
  per matrix instead of (S+1)·M, and pool distances reduce to r×r Gram
  contractions (DESIGN.md §13, kernels/pool_distance.py factor_gram).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

PyTree = Any
F32 = jnp.float32


def tree_zeros_like_stacked(params: PyTree, capacity: int) -> PyTree:
    return jax.tree.map(
        lambda p: jnp.zeros((capacity,) + p.shape, p.dtype), params)


def tree_set_member(stack: PyTree, params: PyTree, idx) -> PyTree:
    return jax.tree.map(
        lambda s, p: jax.lax.dynamic_update_index_in_dim(
            s, p.astype(s.dtype), idx, 0), stack, params)


def tree_get_member(stack: PyTree, idx) -> PyTree:
    return jax.tree.map(
        lambda s: jax.lax.dynamic_index_in_dim(s, idx, 0, keepdims=False),
        stack)


class ModelPool(NamedTuple):
    """Paper-faithful pool. `members`: stacked pytree (capacity leading axis);
    `count`: int32 scalar (live members). Capacity is the static leading dim
    of every member leaf (kept out of the pytree so jit sees it as static)."""
    members: PyTree
    count: jax.Array

    @classmethod
    @jax.named_scope(obs.POOL_CREATE)
    def create(cls, m0: PyTree, capacity: int) -> "ModelPool":
        stack = tree_zeros_like_stacked(m0, capacity)
        stack = tree_set_member(stack, m0, 0)
        return cls(stack, jnp.int32(1))

    @property
    def capacity(self) -> int:
        return jax.tree.leaves(self.members)[0].shape[0]

    @jax.named_scope(obs.POOL_APPEND)
    def append(self, params: PyTree) -> "ModelPool":
        return self._replace(
            members=tree_set_member(self.members, params, self.count),
            count=self.count + 1)

    def mask(self) -> jax.Array:
        return (jnp.arange(self.capacity) < self.count).astype(F32)

    @jax.named_scope(obs.POOL_AVERAGE)
    def average(self) -> PyTree:
        """Eq. 5/6: masked mean over live members."""
        w = self.mask() / self.count.astype(F32)

        def avg(s):
            wf = w.reshape((self.capacity,) + (1,) * (s.ndim - 1))
            return jnp.sum(s.astype(F32) * wf, axis=0).astype(s.dtype)
        return jax.tree.map(avg, self.members)

    def first(self) -> PyTree:
        """m_0^i — the d2 anchor."""
        return tree_get_member(self.members, 0)


class MomentPool(NamedTuple):
    """Moment-form pool statistics (squared-L2 regularizer only)."""
    mean: PyTree           # μ, f32
    sq_norm_mean: jax.Array  # q = mean_t ||w_t||², f32 scalar
    count: jax.Array
    anchor: PyTree         # m_0^i (kept exactly — d2 needs it)

    @classmethod
    @jax.named_scope(obs.POOL_CREATE)
    def create(cls, m0: PyTree) -> "MomentPool":
        mean = jax.tree.map(lambda p: p.astype(F32), m0)
        q = _sq_norm(m0)
        return cls(mean, q, jnp.int32(1), m0)

    @jax.named_scope(obs.POOL_APPEND)
    def append(self, params: PyTree) -> "MomentPool":
        """Left-fold incremental update: μ ← (n·μ + w)/(n+1) applied in
        append order. Mathematically this equals the stacked pool's masked
        mean Σ w_t / n for every append order, but the float association
        differs (a running fold vs one masked sum), so ``average()``
        agrees with ``ModelPool.average()`` to rounding tolerance, not
        bitwise — pinned by the k-append property test in tests/test_api.py."""
        n = self.count.astype(F32)
        new_mean = jax.tree.map(
            lambda m, p: (m * n + p.astype(F32)) / (n + 1), self.mean, params)
        new_q = (self.sq_norm_mean * n + _sq_norm(params)) / (n + 1)
        return MomentPool(new_mean, new_q, self.count + 1, self.anchor)

    @jax.named_scope(obs.POOL_AVERAGE)
    def average(self) -> PyTree:
        return jax.tree.map(lambda m, a: m.astype(a.dtype),
                            self.mean, self.anchor)

    def first(self) -> PyTree:
        return self.anchor

    def mean_sq_distance(self, params: PyTree) -> jax.Array:
        """mean_t ||w − w_t||² = ||w||² − 2⟨w,μ⟩ + q (exact)."""
        wsq = _sq_norm(params)
        dot = sum(jnp.sum(p.astype(F32) * m)
                  for p, m in zip(jax.tree.leaves(params),
                                  jax.tree.leaves(self.mean)))
        return jnp.maximum(wsq - 2.0 * dot + self.sq_norm_mean, 0.0)


def _sq_norm(tree: PyTree) -> jax.Array:
    return sum(jnp.sum(jnp.square(x.astype(F32)))
               for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Low-rank delta pool (DESIGN.md §13)
# ---------------------------------------------------------------------------

# A leaf is factored when its trailing two dims form a real matrix; smaller
# trailing dims (biases, norm scales, stacked per-layer vectors) stay dense
# deltas — their bytes are negligible and rank-r factors would not compress
# them. Leading dims (e.g. the scanned transformer layer axis L on
# (L, d_in, d_out) leaves) are treated as a batch of matrices.
FACTOR_MIN = 8

# Trace-time constant seed for the randomized range-finder's projection Ω.
# Folding in the leaf index makes every leaf's Ω a *pure function of the
# leaf position* — append is deterministic across jit/scan/vmap/shard_map
# with no RNG state threaded through the pool pytree.
_OMEGA_SEED = 20240412


def _leaf_key(i: int) -> str:
    """Stable dict key for base-leaf index i (zero-padded so jax's sorted
    dict-key pytree order equals leaf order)."""
    return f"{i:04d}"


def _is_factored(shape) -> bool:
    return len(shape) >= 2 and min(shape[-2:]) >= FACTOR_MIN


def _project_delta(delta: jax.Array, r: int, leaf_idx: int):
    """Randomized range-finder: project delta (…, d_in, d_out) onto its
    best-effort rank-r approximation U @ Vᵀ with U (…, d_in, r) orthonormal.

    Y = Δ·Ω (Ω Gaussian, fixed per leaf), Q = qr(Y), U = Q, V = ΔᵀQ —
    the reconstruction QQᵀΔ is the projection of Δ onto range(Q). At full
    rank r = min(d_in, d_out) the projection is exact (Q spans range(Δ):
    Ω is square+generic when d_out = r, and Q is a complete orthonormal
    basis when d_in = r), which the engine-level equivalence tests pin."""
    key = jax.random.fold_in(jax.random.PRNGKey(_OMEGA_SEED), leaf_idx)
    omega = jax.random.normal(key, (delta.shape[-1], r), F32)
    y = jnp.einsum("...io,or->...ir", delta, omega)
    q, _ = jnp.linalg.qr(y)                       # (…, d_in, r)
    v = jnp.einsum("...io,...ir->...or", delta, q)
    return q, v


class LeafDelta(NamedTuple):
    """One base leaf's per-member delta in pool-native form: factor stacks
    (u, v) for matrix leaves, a dense stack for the rest — exactly one side
    is populated. A NamedTuple so a params-structured tree of these is
    itself a pytree: jit/vmap see the factor arrays as leaves and the
    (static) structure tells a factored forward which form each site has
    (DESIGN.md §14)."""
    u: Any        # (C, *lead, d_in, r) f32, or None for dense leaves
    v: Any        # (C, *lead, d_out, r) f32, or None for dense leaves
    dense: Any    # (C, *shape) f32, or None for factored leaves


class LowRankDeltaPool(NamedTuple):
    """Factor-form pool: member t reconstructs as base + U_t @ V_tᵀ per
    matrix leaf (dense delta for the rest). Member 0 is the base itself
    (zero factors), mirroring ModelPool.create's seeding.

    ``u``/``v``/``dense`` are dicts keyed by zero-padded base-leaf index
    (`_leaf_key`); their leading axis is the static capacity, like
    ``ModelPool.members`` — so vmap/scan/unstack treat this pool exactly
    like the stacked one. Per-leaf rank is min(pool rank, d_in, d_out),
    recoverable from the factor shapes (``rank`` property)."""
    base: PyTree                 # m0, original dtypes
    u: Dict[str, jax.Array]      # (C, *lead, d_in, r_leaf) f32
    v: Dict[str, jax.Array]      # (C, *lead, d_out, r_leaf) f32
    dense: Dict[str, jax.Array]  # (C, *shape) f32 — non-matrix leaves
    count: jax.Array

    @classmethod
    @jax.named_scope(obs.POOL_CREATE)
    def create(cls, m0: PyTree, capacity: int,
               rank: int) -> "LowRankDeltaPool":
        u, v, dense = {}, {}, {}
        for i, p in enumerate(jax.tree.leaves(m0)):
            k = _leaf_key(i)
            if _is_factored(p.shape):
                r = min(rank, p.shape[-2], p.shape[-1])
                u[k] = jnp.zeros((capacity,) + p.shape[:-1] + (r,), F32)
                v[k] = jnp.zeros(
                    (capacity,) + p.shape[:-2] + (p.shape[-1], r), F32)
            else:
                dense[k] = jnp.zeros((capacity,) + p.shape, F32)
        return cls(m0, u, v, dense, jnp.int32(1))

    @property
    def capacity(self) -> int:
        stacks = list(self.u.values()) + list(self.dense.values())
        return stacks[0].shape[0]

    @property
    def rank(self) -> int:
        """The configured rank ceiling (max per-leaf factor rank)."""
        return max([a.shape[-1] for a in self.u.values()] or [0])

    @jax.named_scope(obs.POOL_APPEND)
    def append(self, params: PyTree) -> "LowRankDeltaPool":
        """Truncated-rank append: Δ = params − base, each matrix leaf
        projected onto rank r via the randomized range-finder."""
        u, v, dense = dict(self.u), dict(self.v), dict(self.dense)
        for i, (b, p) in enumerate(zip(jax.tree.leaves(self.base),
                                       jax.tree.leaves(params))):
            k = _leaf_key(i)
            delta = p.astype(F32) - b.astype(F32)
            if k in dense:
                dense[k] = jax.lax.dynamic_update_index_in_dim(
                    dense[k], delta, self.count, 0)
            else:
                ui, vi = _project_delta(delta, u[k].shape[-1], i)
                u[k] = jax.lax.dynamic_update_index_in_dim(
                    u[k], ui, self.count, 0)
                v[k] = jax.lax.dynamic_update_index_in_dim(
                    v[k], vi, self.count, 0)
        return self._replace(u=u, v=v, dense=dense, count=self.count + 1)

    def mask(self) -> jax.Array:
        return (jnp.arange(self.capacity) < self.count).astype(F32)

    @jax.named_scope(obs.POOL_AVERAGE)
    def average(self) -> PyTree:
        """Eq. 5/6 masked mean — the ONE place factors densify on the
        training path: base + Σ_t w_t·U_tV_tᵀ, reconstructed lazily per
        handoff/init (once per pool slot, not per SGD step)."""
        w = self.mask() / self.count.astype(F32)
        out = []
        for i, b in enumerate(jax.tree.leaves(self.base)):
            k = _leaf_key(i)
            if k in self.dense:
                d = jnp.einsum("c,c...->...", w, self.dense[k])
            else:
                d = jnp.einsum("c,c...ir,c...jr->...ij",
                               w, self.u[k], self.v[k])
            out.append((b.astype(F32) + d).astype(b.dtype))
        return jax.tree.unflatten(jax.tree.structure(self.base), out)

    def first(self) -> PyTree:
        """m_0^i — the d2 anchor. Member 0's delta is zero by
        construction, so this is the base, exactly."""
        return self.base

    def member(self, t) -> PyTree:
        """Densify member t: base + U_tV_tᵀ (dense delta elsewhere)."""
        out = []
        for i, b in enumerate(jax.tree.leaves(self.base)):
            k = _leaf_key(i)
            if k in self.dense:
                d = self.dense[k][t]
            else:
                d = jnp.einsum("...ir,...jr->...ij", self.u[k][t],
                               self.v[k][t])
            out.append((b.astype(F32) + d).astype(b.dtype))
        return jax.tree.unflatten(jax.tree.structure(self.base), out)

    def delta_tree(self) -> PyTree:
        """The pool's deltas re-hung on the base params structure: a pytree
        shaped like ``base`` whose every leaf position holds a `LeafDelta`
        (factor stacks for matrix leaves, the dense stack otherwise). This
        is the factored-serving handoff (`PoolServer.from_pool` keeps
        factor form for models with a `forward_factored` hook, DESIGN.md
        §14): a factored forward walks base params and deltas together —
        ``deltas["layers"]["attn"]["wq"].u`` sits exactly where
        ``params["layers"]["attn"]["wq"]`` does — so serving memory stays
        M + C·r·(d_in+d_out) instead of the C·M densified stack."""
        out = []
        for i in range(len(jax.tree.leaves(self.base))):
            k = _leaf_key(i)
            if k in self.dense:
                out.append(LeafDelta(None, None, self.dense[k]))
            else:
                out.append(LeafDelta(self.u[k], self.v[k], None))
        return jax.tree.unflatten(jax.tree.structure(self.base), out)

    def materialize_members(self) -> PyTree:
        """The full stacked member pytree (C leading axis) — the DENSE
        serving handoff (`PoolServer.from_pool` for models without a
        factored forward, and the factored path's correctness oracle):
        scoring then vmaps forwards over stacked members at C·M serving
        memory. Models with a `forward_factored` hook serve from
        `delta_tree()` instead (DESIGN.md §14)."""
        out = []
        for i, b in enumerate(jax.tree.leaves(self.base)):
            k = _leaf_key(i)
            if k in self.dense:
                d = self.dense[k]
            else:
                d = jnp.einsum("c...ir,c...jr->c...ij", self.u[k], self.v[k])
            out.append((b[None].astype(F32) + d).astype(b.dtype))
        return jax.tree.unflatten(jax.tree.structure(self.base), out)


def pool_nbytes(pool) -> int:
    """Total bytes of the pool's leaf arrays — the benchmarks'
    memory-footprint metric (benchmarks/pool_memory.py)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool)
               if hasattr(x, "dtype"))
