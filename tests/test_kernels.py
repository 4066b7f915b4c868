"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp oracles in repro.kernels.ref (kernels run in interpret mode on CPU
— same kernel body the TPU target compiles)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # clean env: deterministic example sweep
    from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.pool_distance import distances_from_stats

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# attention: the model's path (the jnp scan off-TPU) and the TPU kernel
# (jax's splash attention, interpreted) against the dense reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,tq,tk,h,kv,hd", [
    (1, 32, 32, 4, 4, 16),     # MHA
    (2, 64, 64, 8, 2, 32),     # GQA 4x
    (1, 48, 96, 4, 1, 64),     # MQA, tk > tq
    (2, 128, 128, 4, 4, 128),  # MXU-aligned
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(b, tq, tk, h, kv, hd, dtype, causal):
    from repro.models.layers import flash_attention
    ks = jax.random.split(jax.random.fold_in(KEY, tq * tk * h), 3)
    q = jax.random.normal(ks[0], (b, tq, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, tk, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, tk, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, kv_block=32)
    gold = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gold, np.float32), **_tol(dtype))


def test_flash_attention_sliding_window():
    from repro.models.layers import flash_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 4, 32))
    v = jax.random.normal(ks[2], (1, 64, 4, 32))
    out = flash_attention(q, k, v, causal=True, window=16, kv_block=16)
    gold = ref.attention_ref(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               rtol=2e-5, atol=2e-5)


def test_attention_kernel_matches_reference():
    """The TPU kernel, GQA 2x over one 128-token block, against the f32
    reference on the same bf16 inputs."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 128, 2, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 128, 2, 128), jnp.bfloat16)
    out = ops.attention(q, k, v, causal=True)
    gold = ref.attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gold), **_tol(jnp.bfloat16))


# ---------------------------------------------------------------------------
# pool distance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c,p", [(2, 1000), (6, 70000), (11, 131072)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("measure", ["l2", "l1", "cosine", "squared_l2"])
def test_pool_distance(c, p, dtype, measure):
    ks = jax.random.split(jax.random.fold_in(KEY, c * p), 2)
    w = jax.random.normal(ks[0], (p,), dtype)
    pool = jax.random.normal(ks[1], (c, p), dtype)
    d = ops.pool_distances(w, pool, measure=measure)
    gold_stats = ref.pool_distance_ref(w, pool)
    w_sq = jnp.sum(jnp.square(w.astype(jnp.float32)))
    gold = distances_from_stats(gold_stats, w_sq, measure)
    np.testing.assert_allclose(np.asarray(d), np.asarray(gold),
                               rtol=1e-3 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


@given(b=st.integers(1, 4), c=st.integers(1, 5), p=st.integers(1, 700),
       block_pow=st.integers(5, 9))
@settings(max_examples=20, deadline=None)
def test_pool_distance_stats_batched_matches_per_run_loop(b, c, p, block_pow):
    """Property: the batched (B, C, P) kernel sweep equals a Python loop of
    per-run (C, P) calls — including the ragged-padding edge where P is not
    a multiple of block_p (the zero-padded tail must not leak into any
    stat)."""
    from repro.core.distances import pool_distance_stats_ref
    from repro.kernels.pool_distance import pool_distance_stats
    block_p = 2 ** block_pow            # 32 … 512, mostly not dividing p
    ks = jax.random.split(jax.random.fold_in(KEY, b * 7919 + c * 131 + p), 2)
    w = jax.random.normal(ks[0], (b, p))
    pool = jax.random.normal(ks[1], (b, c, p))
    got = pool_distance_stats(w, pool, block_p=block_p, interpret=True)
    for v in got.values():
        assert v.shape == (b, c)
    for i in range(b):                  # per-run unbatched kernel calls
        one = pool_distance_stats(w[i], pool[i], block_p=block_p,
                                  interpret=True)
        for k in got:
            np.testing.assert_allclose(np.asarray(got[k][i]),
                                       np.asarray(one[k]),
                                       rtol=1e-5, atol=1e-4, err_msg=k)
    refd = pool_distance_stats_ref(w, pool)   # jnp reference path
    for k in got:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(refd[k]),
                                   rtol=1e-5, atol=1e-4, err_msg=k)


def test_pool_distances_batched_front_end():
    """ops.pool_distances accepts the run_batch stacked shapes and agrees
    with the single-run path for every measure."""
    ks = jax.random.split(KEY, 2)
    w = jax.random.normal(ks[0], (3, 2000))
    pool = jax.random.normal(ks[1], (3, 4, 2000))
    for measure in ("l2", "l1", "cosine", "squared_l2"):
        batched = ops.pool_distances(w, pool, measure=measure)
        assert batched.shape == (3, 4)
        for i in range(3):
            one = ops.pool_distances(w[i], pool[i], measure=measure)
            np.testing.assert_allclose(np.asarray(batched[i]),
                                       np.asarray(one), rtol=1e-5,
                                       atol=1e-5, err_msg=measure)


def test_pool_distance_matches_core_d1():
    """Fused kernel agrees with repro.core.distances.d1_pool_distance."""
    from repro.core import ModelPool, d1_pool_distance
    from repro.kernels.ops import tree_pool_distances
    params = {"a": jax.random.normal(KEY, (37, 13)),
              "b": {"c": jax.random.normal(jax.random.fold_in(KEY, 1), (91,))}}
    pool = ModelPool.create(params, capacity=4)
    pool = pool.append(jax.tree.map(lambda x: x + 0.1, params))
    pool = pool.append(jax.tree.map(lambda x: x * 0.7, params))
    live = jax.tree.map(lambda x: x - 0.05, params)
    gold = d1_pool_distance(live, pool, "l2")
    dists = tree_pool_distances(live, pool.members, measure="l2")
    mask = np.asarray(pool.mask())
    fused = float((np.asarray(dists) * mask).sum() / mask.sum())
    np.testing.assert_allclose(fused, float(gold), rtol=1e-5)


# ---------------------------------------------------------------------------
# chunked GLA scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,h,kd,vd,chunk", [
    (1, 32, 2, 8, 8, 8),
    (2, 64, 3, 16, 32, 16),
    (1, 128, 2, 64, 64, 32),
])
@pytest.mark.parametrize("mode", ["mamba2", "rwkv6"])
def test_gla_chunked_kernel(b, t, h, kd, vd, chunk, mode):
    ks = jax.random.split(jax.random.fold_in(KEY, t * h * kd), 5)
    q = jax.random.normal(ks[0], (b, t, h, kd))
    k = jax.random.normal(ks[1], (b, t, h, kd))
    v = jax.random.normal(ks[2], (b, t, h, vd))
    if mode == "mamba2":
        ld = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
        y, s = ops.gla_chunked(q, k, v, ld, chunk=chunk)
        yg, sg = ref.gla_recurrence_ref(q, k, v, ld)
    else:
        ld = -jnp.exp(jax.random.normal(ks[3], (b, t, h, kd)) - 1.0)
        u = jnp.exp(0.1 * jax.random.normal(ks[4], (h, kd)))
        y, s = ops.gla_chunked(q, k, v, ld, chunk=chunk, pre=True, bonus=u)
        yg, sg = ref.gla_recurrence_ref(q, k, v, ld, bonus=u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yg),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sg),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["mamba2", "rwkv6"])
def test_gla_jnp_matches_ref(mode):
    """models.ssm.gla_chunked (the path every model lowers) vs naive rec."""
    from repro.models.ssm import gla_chunked as gla_jnp
    ks = jax.random.split(KEY, 5)
    b, t, h, kd, vd = 2, 96, 2, 8, 16
    q = jax.random.normal(ks[0], (b, t, h, kd))
    k = jax.random.normal(ks[1], (b, t, h, kd))
    v = jax.random.normal(ks[2], (b, t, h, vd))
    if mode == "mamba2":
        ld = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
        y, s = gla_jnp(q, k, v, ld, chunk=32)
        yg, sg = ref.gla_recurrence_ref(q, k, v, ld)
    else:
        ld = -jnp.exp(jax.random.normal(ks[3], (b, t, h, kd)) - 1.0)
        u = jnp.exp(0.1 * jax.random.normal(ks[4], (h, kd)))
        y, s = gla_jnp(q, k, v, ld, chunk=32, bonus=u)
        yg, sg = ref.gla_recurrence_ref(q, k, v, ld, bonus=u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yg),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sg),
                               rtol=1e-4, atol=1e-4)


def test_gla_decode_step_matches_ref():
    from repro.models.ssm import gla_step
    ks = jax.random.split(KEY, 5)
    b, h, kd, vd = 2, 3, 8, 16
    q = jax.random.normal(ks[0], (b, 1, h, kd))
    k = jax.random.normal(ks[1], (b, 1, h, kd))
    v = jax.random.normal(ks[2], (b, 1, h, vd))
    ld = -jnp.exp(jax.random.normal(ks[3], (b, 1, h, kd)))
    state = jax.random.normal(ks[4], (b, h, kd, vd))
    y, s = gla_step(q[:, 0], k[:, 0], v[:, 0], ld[:, 0], state)
    yg, sg = ref.gla_recurrence_ref(q, k, v, ld, initial_state=state)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yg[:, 0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sg),
                               rtol=1e-5, atol=1e-5)
