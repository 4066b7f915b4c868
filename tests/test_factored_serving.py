"""Tests for factored ensemble serving (`models/factored.py`,
`kernels/bgmv.py`, the `PoolServer` factor path — DESIGN.md §14).

Six groups:

1. *BGMV kernel* — hypothesis: the blocked Pallas kernel (interpret mode
   off-TPU) against `kernels.ref.bgmv_ref`, shared and per-member x,
   ragged N tails, a tiled and padded d_out axis, within the f32 error
   bound of two chained GEMMs; the `ops.bgmv` routing wrapper agrees
   with the ref.
2. *Factored ≡ densified, every rank* — the factored transformer scoring
   path (shared-base forward + BGMV corrections) matches the densified
   vmap oracle at ANY rank: both read the same pool factors, so
   truncation cannot open a gap — only float reassociation can
   (~1e-6 observed; pinned at 2e-5 relative). Tied AND untied unembed.
3. *Full-rank exactness* — at r ≥ min(d_in, d_out) per leaf the factored
   server reproduces a python loop over the ORIGINAL appended member
   params (the range-finder projection is the identity at full rank).
4. *Server plumbing on a factored server* — bucketed `score` matches
   `score_batch` on the gathered rows to reassociation tolerance (the
   padded bucket is another compiled program); weight changes never
   recompile;
   `weight_fn` hooks receive the `FactoredMembers` NamedTuple;
   majority-vote mass is 1.0 per request; checkpoint round-trip serves
   bit-identically (factor leaves restore bit-exactly).
5. *Custom-model hook* — a probe MLP wires `forward_factored` from
   `fdense` alone (the benchmarks/common.py pattern) and matches its
   densified oracle at every rank.
6. *Routing* — hookless models auto-fall-back to the densified path;
   `factored=True` without the hook raises; `FactoredMembers` handed to
   a hookless server raises.
"""
import dataclasses
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.checkpoint import save_pool
from repro.configs import get_arch
from repro.core.pool import LowRankDeltaPool
from repro.kernels import bgmv as bgmv_mod
from repro.kernels import ops
from repro.kernels.bgmv import bgmv_pallas
from repro.kernels.ref import bgmv_ref
from repro.models import build_model
from repro.models.factored import FACTORED_FORWARD_ATTR, fdense
from repro.serve import PoolServer
from repro.serve.engine import FactoredMembers

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# 1. BGMV kernel vs the jnp oracle
# ---------------------------------------------------------------------------

def _assert_bgmv_close(got, x, u, v):
    """Kernel vs `bgmv_ref` within the f32 error bound of the two chained
    GEMMs: a reassociated length-k dot product is off by at most
    k·(eps/2)·Σ|terms| (Higham's γ_k), so (x@u)@vᵀ on either side is off
    by at most (d_in + r)·(eps/2)·(|x|@|u|@|v|ᵀ), and the two sides by
    twice that."""
    want = np.asarray(bgmv_ref(x, u, v))
    assert got.shape == want.shape
    k = u.shape[1] + u.shape[2]
    bound = np.asarray(bgmv_ref(jnp.abs(x), jnp.abs(u), jnp.abs(v)))
    tol = k * np.finfo(np.float32).eps * bound
    excess = np.abs(np.asarray(got) - want) - tol
    assert excess.max() <= 0, float(excess.max())


@given(s=st.integers(1, 4), n=st.integers(1, 70), d_in=st.integers(3, 17),
       d_out=st.integers(3, 17), r=st.integers(1, 5),
       shared=st.booleans(), seed=st.integers(0, 10))
@settings(max_examples=40, deadline=None)
def test_bgmv_kernel_matches_ref(s, n, d_in, d_out, r, shared, seed):
    """Interpret-mode kernel vs `bgmv_ref`, both x layouts, with a
    block_n small enough that ragged tails (zero-pad + slice) are
    exercised at every n."""
    key = jax.random.fold_in(KEY, seed)
    kx, ku, kv = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, d_in) if shared else (s, n, d_in))
    u = jax.random.normal(ku, (s, d_in, r))
    v = jax.random.normal(kv, (s, d_out, r))
    got = bgmv_pallas(x, u, v, block_n=16, interpret=True)
    assert got.shape == (s, n, d_out)
    _assert_bgmv_close(got, x, u, v)


@pytest.mark.parametrize("d_out,shared", [(512, False), (640, True),
                                          (1000, False)])
def test_bgmv_tiles_output_axis(monkeypatch, d_out, shared):
    """With BLOCK_O cut to 256 the d_out axis runs as a grid axis: 512
    splits into 256-wide tiles, 640 into its largest lane-aligned divisor
    (128), and 1000 (not lane-aligned) pads to 1024 and is sliced back.
    The x @ u projection kept in scratch must serve every d_out tile."""
    monkeypatch.setattr(bgmv_mod, "BLOCK_O", 256)
    assert bgmv_mod._out_block(d_out) == {512: 256, 640: 128, 1000: 256}[d_out]
    kx, ku, kv = jax.random.split(jax.random.fold_in(KEY, d_out), 3)
    s, n, d_in, r = 3, 40, 24, 8
    x = jax.random.normal(kx, (n, d_in) if shared else (s, n, d_in))
    u = jax.random.normal(ku, (s, d_in, r))
    v = jax.random.normal(kv, (s, d_out, r))
    got = bgmv_pallas(x, u, v, block_n=16, interpret=True)
    _assert_bgmv_close(got, x, u, v)


def test_ops_bgmv_routing_agrees_with_ref():
    """The production wrapper (jnp twin off-TPU, Mosaic on TPU) computes
    the same correction as the oracle on both x layouts."""
    kx, ku, kv = jax.random.split(KEY, 3)
    u = jax.random.normal(ku, (3, 12, 4))
    v = jax.random.normal(kv, (3, 9, 4))
    for x in (jax.random.normal(kx, (7, 12)),
              jax.random.normal(kx, (3, 7, 12))):
        np.testing.assert_allclose(np.asarray(ops.bgmv(x, u, v)),
                                   np.asarray(bgmv_ref(x, u, v)),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Shared transformer fixture: a tiny dense-GQA decoder (the factored
# hook's family) + factor pools built from real param trees.
# ---------------------------------------------------------------------------

TF_CFG = dataclasses.replace(
    get_arch("llama3.2-1b").reduced(),
    n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
    d_ff=64, vocab_size=128)
TF_MODEL = build_model(TF_CFG)
FULL_TF_RANK = 64      # ≥ every per-leaf min(d_in, d_out) at this size


def _tf_pool(rank, n_appends=2, seed=0, capacity=None):
    """A factor pool seeded from one init with `n_appends` appended
    re-inits (deltas shrunk 10× so logits stay O(1) at any rank).
    Returns (pool, [member params incl. base])."""
    key = jax.random.fold_in(KEY, seed)
    base = TF_MODEL.init(key)
    pool = LowRankDeltaPool.create(base, capacity=(capacity or n_appends + 2),
                                   rank=rank)
    members = [base]
    for i in range(n_appends):
        p = TF_MODEL.init(jax.random.fold_in(key, i + 1))
        p = jax.tree.map(lambda a, b: b + 0.1 * (a - b), p, base)
        members.append(p)
        pool = pool.append(p)
    return pool, members


def _tokens(b=3, t=8, seed=7):
    return {"tokens": jax.random.randint(
        jax.random.fold_in(KEY, 1000 + seed), (b, t), 0, TF_CFG.vocab_size)}


# ---------------------------------------------------------------------------
# 2. Factored ≡ densified, every rank
# ---------------------------------------------------------------------------

@given(rank=st.integers(1, 8), seed=st.integers(0, 5))
@settings(max_examples=6, deadline=None)
def test_transformer_factored_matches_densified_every_rank(rank, seed):
    """Both servers read the SAME pool factors — one as (x@U)@Vᵀ
    corrections, one as the densified U@Vᵀ member stack — so they agree
    at every rank, dead slots included (capacity > live: zero deltas
    score as base, weight zero either way)."""
    pool, _ = _tf_pool(rank, seed=seed)
    fac = PoolServer.from_pool(TF_MODEL, pool)
    den = PoolServer.from_pool(TF_MODEL, pool, factored=False)
    assert fac.factored and not den.factored
    assert fac.n_members == den.n_members == int(pool.count)
    batch = _tokens(seed=seed)
    s1, _ = fac.score_batch(batch)
    s2, _ = den.score_batch(batch)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-5, atol=2e-5)


def test_untied_unembed_factored_matches_densified():
    """tie_embeddings=False routes the lm_head delta WITHOUT the tied
    transpose role-swap — pin the untied branch too."""
    cfg = dataclasses.replace(TF_CFG, tie_embeddings=False)
    model = build_model(cfg)
    base = model.init(KEY)
    pool = LowRankDeltaPool.create(base, capacity=3, rank=4)
    p = model.init(jax.random.fold_in(KEY, 1))
    pool = pool.append(jax.tree.map(lambda a, b: b + 0.1 * (a - b), p, base))
    batch = {"tokens": jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)}
    s1, _ = PoolServer.from_pool(model, pool).score_batch(batch)
    s2, _ = PoolServer.from_pool(model, pool,
                                 factored=False).score_batch(batch)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# 3. Full-rank exactness against the original members
# ---------------------------------------------------------------------------

def test_full_rank_factored_matches_true_member_forwards():
    """At full per-leaf rank the range-finder projection is the identity,
    so the factored ensemble equals a python loop of `model.forward` over
    the ORIGINAL appended params (masked weighted mean) — not just the
    densified pool. f32 QR round-trip headroom: 1e-4."""
    pool, members = _tf_pool(FULL_TF_RANK)
    srv = PoolServer.from_pool(TF_MODEL, pool)
    assert srv.factored
    batch = _tokens()
    scores, _ = srv.score_batch(batch)
    logits = jnp.stack([TF_MODEL.forward(m, batch) for m in members])
    want = logits.mean(0)          # uniform mask over the live slots
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# 4. Server plumbing on a factored server
# ---------------------------------------------------------------------------

def _factored_fixture():
    pool, _ = _tf_pool(4)
    srv = PoolServer.from_pool(TF_MODEL, pool, buckets=(1, 4))
    arrays = {"tokens": jax.random.randint(jax.random.fold_in(KEY, 77),
                                           (20, 8), 0, TF_CFG.vocab_size)}
    return srv, arrays


_FACTORED_FIXTURE = _factored_fixture()


@given(n=st.integers(1, 10), seed=st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_factored_bucketed_scoring_matches_unbatched(n, seed):
    """Bucketing pads each chunk to its bucket and drops the pad rows, so
    the real rows must score as in the unbatched call. The padded chunk
    runs another compiled program (another row count), and the compiler
    may block its GEMMs differently, so the two agree to reassociation
    tolerance, not bitwise: the logits are O(0.1) sums of f32 dot
    products of at most 64 terms, whose reassociation error is below
    64·eps·Σ|terms| ≈ 1e-6 here. Predictions must agree wherever the
    reference's top-two margin exceeds twice that tolerance."""
    srv, arrays = _FACTORED_FIXTURE
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, arrays["tokens"].shape[0], size=n).astype(np.int32)
    scores, preds = srv.score(arrays, idx)
    gathered = {k: a[jnp.asarray(idx)] for k, a in arrays.items()}
    ref_scores, ref_preds = (np.asarray(a) for a in srv.score_batch(gathered))
    atol = 1e-6
    np.testing.assert_allclose(scores, ref_scores, rtol=0, atol=atol)
    top2 = np.sort(ref_scores, axis=-1)[..., -2:]
    clear = top2[..., 1] - top2[..., 0] > 2 * atol
    np.testing.assert_array_equal(preds[clear], ref_preds[clear])


def test_factored_weight_change_never_recompiles():
    """Weights are a traced input of the one compiled factored program —
    re-weighting the ensemble must not add cache entries."""
    srv, arrays = _FACTORED_FIXTURE
    batch = {k: a[:2] for k, a in arrays.items()}
    srv.score_batch(batch)
    before = srv._score_batch._cache_size()
    srv.weights = srv.weights * jnp.asarray([0.5, 1.0, 2.0, 0.0])
    srv.score_batch(batch)
    assert srv._score_batch._cache_size() == before


def test_factored_weight_fn_sees_factored_members():
    """The density-weighting hook receives the `FactoredMembers`
    NamedTuple on a factored server; a uniform rescale cancels in the
    normalized reduction bit-exactly (power-of-two scale)."""
    pool, _ = _tf_pool(4)
    seen = {}

    def hook(members, mask):
        seen["members"] = members
        return mask * 2.0

    srv = PoolServer.from_pool(TF_MODEL, pool, weight_fn=hook)
    assert isinstance(seen["members"], FactoredMembers)
    batch = _tokens()
    s1, _ = srv.score_batch(batch)
    s2, _ = PoolServer.from_pool(TF_MODEL, pool).score_batch(batch)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_factored_reductions_match_hand_loop():
    """mean_logits recomputed from per-member factored logits; vote mass
    is exactly 1.0 per (request, position) under the normalized
    majority-vote contract."""
    pool, _ = _tf_pool(4)
    batch = _tokens()
    srv = PoolServer.from_pool(TF_MODEL, pool)
    hook = getattr(TF_MODEL.forward, FACTORED_FORWARD_ATTR)
    logits = hook(srv.members.base, srv.members.deltas, batch)
    w = srv.weights.reshape((-1,) + (1,) * (logits.ndim - 1))
    want = (w * logits).sum(0) / srv.weights.sum()
    scores, preds = srv.score_batch(batch)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(preds),
                                  np.argmax(np.asarray(want), -1))
    mv = PoolServer.from_pool(TF_MODEL, pool, mode="majority_vote")
    votes, _ = mv.score_batch(batch)
    np.testing.assert_allclose(np.asarray(votes).sum(-1), 1.0, rtol=1e-6)


def test_factored_checkpoint_roundtrip_serves_bit_identical(tmp_path):
    """save_pool → load_pool restores factor leaves bit-exactly, and
    `from_checkpoint` auto-routes back onto the factored path — so the
    restored server is bit-identical, not merely close."""
    pool, _ = _tf_pool(4)
    path = str(tmp_path / "tf_pool.npz")
    save_pool(path, pool)
    direct = PoolServer.from_pool(TF_MODEL, pool)
    served = PoolServer.from_checkpoint(TF_MODEL, path, TF_MODEL.init(KEY))
    assert served.factored
    batch = _tokens()
    s1, p1 = direct.score_batch(batch)
    s2, p2 = served.score_batch(batch)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


# ---------------------------------------------------------------------------
# 5. Custom-model hook: a probe MLP built from fdense alone
# ---------------------------------------------------------------------------

TinyModel = namedtuple("TinyModel", "init loss_fn forward")


def _probe_model(with_hook):
    """(16, 12) → relu → (12, 10): both matrices clear FACTOR_MIN, biases
    ride the dense-delta path. The hook mirrors benchmarks/common.py —
    shared x into the first fdense, per-member activations after."""
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"fc1": {"w": 0.5 * jax.random.normal(k1, (16, 12)),
                        "b": jnp.zeros((12,))},
                "fc2": {"w": 0.5 * jax.random.normal(k2, (12, 10)),
                        "b": jnp.zeros((10,))}}

    def forward(params, batch):
        h = jax.nn.relu(batch["x"] @ params["fc1"]["w"]
                        + params["fc1"]["b"])
        return h @ params["fc2"]["w"] + params["fc2"]["b"]

    def forward_factored(params, deltas, batch):
        h = jax.nn.relu(fdense(batch["x"], params["fc1"]["w"],
                               deltas["fc1"]["w"],
                               params["fc1"]["b"], deltas["fc1"]["b"]))
        return fdense(h, params["fc2"]["w"], deltas["fc2"]["w"],
                      params["fc2"]["b"], deltas["fc2"]["b"])

    if with_hook:
        setattr(forward, FACTORED_FORWARD_ATTR, forward_factored)
    return TinyModel(init, None, forward)


def _probe_pool(model, rank, n_appends=3, seed=0):
    key = jax.random.fold_in(KEY, 2000 + seed)
    base = model.init(key)
    pool = LowRankDeltaPool.create(base, capacity=n_appends + 1, rank=rank)
    for i in range(n_appends):
        pool = pool.append(model.init(jax.random.fold_in(key, i + 1)))
    return pool


@given(rank=st.integers(1, 12), seed=st.integers(0, 10))
@settings(max_examples=20, deadline=None)
def test_probe_hook_matches_densified_every_rank(rank, seed):
    model = _probe_model(with_hook=True)
    pool = _probe_pool(model, rank, seed=seed)
    batch = {"x": jax.random.normal(jax.random.fold_in(KEY, 3000 + seed),
                                    (6, 16))}
    fac = PoolServer.from_pool(model, pool)
    den = PoolServer.from_pool(model, pool, factored=False)
    assert fac.factored and not den.factored
    s1, _ = fac.score_batch(batch)
    s2, _ = den.score_batch(batch)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# 6. Routing: fallback and refusal
# ---------------------------------------------------------------------------

def test_hookless_model_falls_back_to_densified():
    model = _probe_model(with_hook=False)
    pool = _probe_pool(model, rank=4)
    srv = PoolServer.from_pool(model, pool)
    assert not srv.factored
    ref = PoolServer(model, pool.materialize_members(), pool.mask())
    batch = {"x": jax.random.normal(KEY, (5, 16))}
    s1, _ = srv.score_batch(batch)
    s2, _ = ref.score_batch(batch)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_factored_true_without_hook_raises():
    model = _probe_model(with_hook=False)
    pool = _probe_pool(model, rank=4)
    with pytest.raises(ValueError, match="forward_factored"):
        PoolServer.from_pool(model, pool, factored=True)


def test_factored_members_require_the_hook():
    model = _probe_model(with_hook=False)
    pool = _probe_pool(model, rank=4)
    with pytest.raises(ValueError, match="hook"):
        PoolServer(model, FactoredMembers(pool.base, pool.delta_tree()),
                   pool.mask())
