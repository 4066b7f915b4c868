"""The pool scoring engine: `PoolServer`.

A trained pool is a stacked pytree of S member models; serving it means
answering "what does the ensemble say about this batch of queries" at
request latency. The server compiles ONE scoring program:

* **vmapped members** — `jax.vmap(model.forward)` over the pool axis, so
  every member scores the batch inside a single jitted call. Transformer
  members run `models/layers.flash_attention` exactly as in training
  (the splash kernel on TPU where the call's shapes allow it, the jnp
  scan otherwise) because the server calls the model's own `forward`.
* **a reduction head** — masked weighted mean of logits (default),
  weighted majority vote, or caller-supplied per-member weights /
  `weight_fn` (the hook ROADMAP item 4's density weighting feeds; weights
  are a traced input, so changing them never recompiles).
* **bucketed request batching** — request counts are rounded up to a
  fixed ladder of bucket sizes (`DEFAULT_BUCKETS`), so a whole traffic
  trace compiles at most `len(buckets)` scoring programs instead of one
  per distinct batch size. Padding rows repeat a real query index and
  are sliced off before anything is returned — a property test pins
  that bucketing never changes outputs.
* **device-resident queries** — like `data/plan.py`, the query pool is
  uploaded once and requests are index gathers *inside* the compiled
  program, not per-request host re-uploads.

Pool-backend note: a `ModelPool` serves all live members; a `MomentPool`
only materializes its running mean (members are not retained by
construction), so its "ensemble" is the single averaged model — same
scoring path, P = 1. A `LowRankDeltaPool` serves in FACTOR form when the
model's forward carries the `models/factored.py` capability hook
(`forward_factored`): the server keeps base params + the pool's
`delta_tree()` (`FactoredMembers`), the compiled scoring program reads the
M-byte base weights once per batch and applies per-member rank-r BGMV
corrections (`kernels/bgmv.py`), and serving memory stays
M + C·r·(d_in+d_out) instead of the C·M densified stack (DESIGN.md §14).
Models without the hook (or `from_pool(..., factored=False)`) fall back to
densifying once at server build (`materialize_members`) and vmapping —
that dense path remains the correctness oracle for the factored one.
Everything above the forward — reduction head, weights/`weight_fn`,
bucketing, device-resident gathers — is identical in both modes.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pool import LowRankDeltaPool, ModelPool, MomentPool
from repro.models.factored import (FACTORED_FORWARD_ATTR,
                                   factored_forward_for)

PyTree = Any
F32 = jnp.float32


class FactoredMembers(NamedTuple):
    """Factor-form serving stack: the shared base params plus the pool's
    `delta_tree()` (a params-structured pytree of `LeafDelta`s, capacity
    leading axis). Stands in for the stacked member pytree wherever the
    server passes `members` — including into `weight_fn` hooks, which see
    this NamedTuple on a factored server."""
    base: PyTree
    deltas: PyTree

# Power-of-~4 ladder: small enough that single requests don't pay a
# 128-wide forward, coarse enough that a trace compiles ≤ 4 programs.
DEFAULT_BUCKETS = (1, 8, 32, 128)

MODES = ("mean_logits", "majority_vote")


def _reduce(mode: str, w: jax.Array, logits: jax.Array) -> jax.Array:
    """(P,) weights × (P, B, ..., C) member logits → (B, ..., C) ensemble
    scores (classifiers emit (P, B, C); LM clients (P, B, T, V)).

    The mean_logits expression is the pinned serving reference: tests
    recompute it from per-member forward calls and assert bit-equality.
    majority_vote normalizes by the same w.sum(), so vote scores are the
    weighted *fraction* of member mass per class (summing to 1 over
    classes) — matching the documented weighted-reduction contract rather
    than scaling with member count.
    """
    wf = w.reshape((w.shape[0],) + (1,) * (logits.ndim - 1))
    if mode == "mean_logits":
        return (wf * logits).sum(0) / w.sum()
    votes = jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1],
                           dtype=logits.dtype)
    return (wf * votes).sum(0) / w.sum()


class PoolServer:
    """One trained pool (or collapsed model) compiled for query scoring.

    `members` is a stacked pytree with a leading pool axis P — or a
    `FactoredMembers` (base + delta tree) for factor-form serving; `mask`
    is a (P,) float32 of live slots (zero-padded slots score with weight
    0). Use the classmethod constructors — `from_pool`, `from_params`,
    `from_result`, `from_checkpoint` — rather than building the stack by
    hand.
    """

    def __init__(self, model, members: PyTree, mask: jax.Array, *,
                 mode: str = "mean_logits",
                 weights: Optional[jax.Array] = None,
                 weight_fn: Optional[Callable[[PyTree, jax.Array],
                                              jax.Array]] = None,
                 buckets: Tuple[int, ...] = DEFAULT_BUCKETS):
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}; expected one of "
                             f"{MODES}")
        buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints; got {buckets}")
        self.model = model
        self.mode = mode
        self.buckets = buckets
        self.members = members
        self.mask = jnp.asarray(mask, F32)
        if weight_fn is not None:
            weights = weight_fn(members, self.mask)
        w = (jnp.asarray(weights, F32) if weights is not None
             else self.mask)
        # dead slots never vote, whatever the hook returned
        self.weights = w * self.mask
        self.n_members = int(self.mask.sum())
        self.factored = isinstance(members, FactoredMembers)
        fwd, mode_ = model.forward, mode
        if self.factored:
            ffwd = factored_forward_for(fwd)
            if ffwd is None:
                raise ValueError(
                    "FactoredMembers given but model.forward has no "
                    f"'{FACTORED_FORWARD_ATTR}' hook (models/factored.py)")

            def member_logits(members, batch):
                # shared-base forward + per-member BGMV corrections; dead
                # slots carry zero deltas, so they score exactly as base —
                # identical to the densified stack's zero-padded slots
                # (their weight is zero either way).
                return ffwd(members.base, members.deltas, batch)
        else:
            def member_logits(members, batch):
                return jax.vmap(lambda m: fwd(m, batch))(members)

        @jax.jit
        def score_batch(members, w, batch):
            scores = _reduce(mode_, w, member_logits(members, batch))
            return scores, jnp.argmax(scores, -1)

        @jax.jit
        def score_idx(members, w, arrays, idx):
            batch = {k: a[idx] for k, a in arrays.items()}
            scores = _reduce(mode_, w, member_logits(members, batch))
            return scores, jnp.argmax(scores, -1)

        self._score_batch = score_batch
        self._score_idx = score_idx

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_pool(cls, model, pool, *, factored: Optional[bool] = None,
                  **kw) -> "PoolServer":
        """Serve a trained pool: every live `ModelPool` member, a
        `LowRankDeltaPool` in factor form (shared-base forward + BGMV
        corrections) when the model carries the `forward_factored` hook —
        densified once otherwise — or the moment-form running mean (P = 1;
        see module docstring).

        `factored`: None (default) auto-routes on the hook; True requires
        it (raises if absent); False forces the densified vmap path (the
        correctness oracle)."""
        if isinstance(pool, ModelPool):
            return cls(model, pool.members, pool.mask(), **kw)
        if isinstance(pool, LowRankDeltaPool):
            hook = factored_forward_for(model.forward)
            if factored is None:
                factored = hook is not None
            if factored:
                if hook is None:
                    raise ValueError(
                        "factored=True but model.forward has no "
                        f"'{FACTORED_FORWARD_ATTR}' hook; use "
                        "factored=False (or None) for the densified path")
                return cls(model,
                           FactoredMembers(pool.base, pool.delta_tree()),
                           pool.mask(), **kw)
            return cls(model, pool.materialize_members(), pool.mask(), **kw)
        if isinstance(pool, MomentPool):
            return cls.from_params(model, pool.average(), **kw)
        raise TypeError(
            f"expected a ModelPool, LowRankDeltaPool or MomentPool, got "
            f"{type(pool).__name__}; for a bare params pytree use "
            "PoolServer.from_params")

    @classmethod
    def from_params(cls, model, params: PyTree, **kw) -> "PoolServer":
        """Serve a single aggregated model (collapsed `tree_mean`/`last`
        serving) through the same compiled path, P = 1."""
        members = jax.tree.map(lambda a: jnp.asarray(a)[None], params)
        return cls(model, members, jnp.ones((1,), F32), **kw)

    @classmethod
    def from_result(cls, model, result, source: str = "pool",
                    **kw) -> "PoolServer":
        """Serve a `RunResult`: its trained pool (`source="pool"`, the
        default — raises the `require_final_pool` diagnosis if the plan
        discarded it) or its aggregated params (`source="params"`)."""
        if source == "params":
            return cls.from_params(model, result.params, **kw)
        if source != "pool":
            raise ValueError(f"source must be 'pool' or 'params', "
                             f"got {source!r}")
        return cls.from_pool(model, result.require_final_pool(), **kw)

    @classmethod
    def from_checkpoint(cls, model, path: str, params_like: PyTree,
                        **kw) -> "PoolServer":
        """Restore a pool saved with `repro.checkpoint.save_pool` straight
        into a server (train → save → load → serve is bit-identical to
        train → serve; a regression test pins this)."""
        from repro.checkpoint import load_pool
        return cls.from_pool(model, load_pool(path, params_like), **kw)

    # -- scoring ------------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest bucket ≥ n (requests beyond the largest bucket are
        served in max-bucket chunks)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def chunk_plan(self, n: int) -> List[Tuple[int, int, int]]:
        """(start, length, bucket) chunks covering an n-request tick."""
        plan, start, cap = [], 0, self.buckets[-1]
        while start < n:
            m = min(cap, n - start)
            plan.append((start, m, self.bucket_for(m)))
            start += m
        return plan

    def score_batch(self, batch: Dict[str, jax.Array]):
        """Score one already-gathered batch dict (no bucketing); returns
        (ensemble scores (B, C), predictions (B,))."""
        return self._score_batch(self.members, self.weights, batch)

    def score(self, arrays: Dict[str, jax.Array], idx) -> Tuple[np.ndarray,
                                                                np.ndarray]:
        """Score requests `idx` (indices into the device-resident query
        pool `arrays`) through the bucketed path. Padding repeats the
        chunk's last real index; the pad rows are dropped on the host
        (an eager device-side slice would compile one program per
        residual size, unbounding the compile set bucketing exists to
        bound), so outputs equal the unbucketed `score_batch` on the
        gathered rows exactly — already host-resident, as responses are.
        """
        idx = np.asarray(idx, np.int32)
        n = len(idx)
        if n == 0:
            raise ValueError("score() needs at least one request index")
        outs = []
        for start, m, bucket in self.chunk_plan(n):
            chunk = idx[start:start + m]
            if m < bucket:
                chunk = np.concatenate(
                    [chunk, np.full(bucket - m, chunk[-1], np.int32)])
            scores, preds = self._score_idx(self.members, self.weights,
                                            arrays, jnp.asarray(chunk))
            outs.append((np.asarray(scores)[:m], np.asarray(preds)[:m]))
        if len(outs) == 1:
            return outs[0]
        return (np.concatenate([s for s, _ in outs]),
                np.concatenate([p for _, p in outs]))

    def warmup(self, arrays: Dict[str, jax.Array],
               sizes) -> None:
        """Compile every bucket a trace will use before timing starts."""
        done = set()
        for n in sizes:
            for _, m, bucket in self.chunk_plan(int(n)):
                if bucket not in done:
                    done.add(bucket)
                    self.score(arrays, np.zeros(bucket, np.int32))
