"""Fused local-step kernel validation (`repro.kernels.local_step`).

Three contracts:

1. *Oracle agreement* — `matmul_blocked` (interpret mode, the same kernel
   body the TPU target compiles) matches `ref.matmul_ref` across ragged
   (M, K, N) × block-size combinations, property-tested; `conv2d_gemm`
   matches the semantically independent `ref.conv2d_ref` (`lax.conv`)
   oracle on the paper CNN's layer shapes, on both the jnp and the
   Pallas-interpret branch, forward AND backward (the custom VJP routes
   grads through the same blocked kernel).
2. *Bit-level twins* — `sgd_update_flat` / `sgd_update_tree` produce the
   exact bits of `ref.sgd_update_ref` / `optimizers.sgd` (the update is
   elementwise; flattening cannot reassociate), and an α=0, β=0
   regularized pool step degenerates bit-for-bit to the plain step.
3. *Engine bit-identity on the conv model* — the paper CNN runs its local
   phases scan-compiled (DataPlans) with params bit-identical to the
   per-step iterator path, sequential and batched — the contract that let
   the `DataPlan(scan=False)` conv carve-out be deleted.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # clean env: deterministic example sweep
    from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.local_step import (FUSED_LOSS_ATTR, conv2d_gemm,
                                      fused_loss_for, matmul_blocked,
                                      maxpool2x2, sgd_update_flat,
                                      sgd_update_tree)

KEY = jax.random.PRNGKey(7)

# the paper CNN's conv stack (3 → w → 2w → 4w at width 64), on a small
# spatial extent so the interpret-mode Pallas sweep stays cheap; every
# channel count is ragged against the 128-wide kernel blocks
PAPER_CNN_LAYERS = [(3, 64), (64, 128), (128, 256)]


# ---------------------------------------------------------------------------
# 1. Oracle agreement
# ---------------------------------------------------------------------------

@given(m=st.integers(1, 70), k=st.integers(1, 70), n=st.integers(1, 70),
       block_pow=st.integers(3, 7))
@settings(max_examples=15, deadline=None)
def test_matmul_blocked_matches_ref(m, k, n, block_pow):
    """Property: the blocked kernel equals the f32 GEMM oracle for any
    (M, K, N), including dims smaller than / not dividing the block —
    the zero-padded tiles must contribute exactly zero."""
    blk = 2 ** block_pow                     # 8 … 128
    ks = jax.random.split(jax.random.fold_in(KEY, m * 83 + k * 7 + n), 2)
    a = jax.random.normal(ks[0], (m, k))
    b = jax.random.normal(ks[1], (k, n))
    out = matmul_blocked(a, b, block_m=blk, block_n=blk, block_k=blk,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cin,cout", PAPER_CNN_LAYERS + [(5, 7)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_conv2d_gemm_matches_lax_conv(cin, cout, use_pallas):
    """im2col + GEMM vs the `lax.conv_general_dilated` oracle on the
    paper CNN's layer shapes plus an odd-channel edge case, on both the
    jnp production branch and the Pallas kernel (interpret mode)."""
    ks = jax.random.split(jax.random.fold_in(KEY, cin * cout), 3)
    x = jax.random.normal(ks[0], (2, 8, 8, cin))
    w = jax.random.normal(ks[1], (3, 3, cin, cout)) / np.sqrt(9 * cin)
    b = 0.1 * jax.random.normal(ks[2], (cout,))
    got = conv2d_gemm(x, w, b, use_pallas=use_pallas, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.conv2d_ref(x, w, b)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_conv2d_gemm_gradients_match_lax_conv(use_pallas):
    """Backward pass: grads through the im2col + GEMM formulation (the
    Pallas branch rides its custom VJP — dA = G·Bᵀ, dB = Aᵀ·G through the
    same blocked kernel) agree with grads through the `lax.conv` oracle
    for x, w and b."""
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (2, 8, 8, 5))
    w = jax.random.normal(ks[1], (3, 3, 5, 6)) / np.sqrt(45)
    b = 0.1 * jax.random.normal(ks[2], (6,))
    t = jax.random.normal(jax.random.fold_in(KEY, 9), (2, 8, 8, 6))

    def loss_gemm(x, w, b):
        y = conv2d_gemm(x, w, b, use_pallas=use_pallas, interpret=True)
        return jnp.mean((y - t) ** 2)

    def loss_ref(x, w, b):
        return jnp.mean((ref.conv2d_ref(x, w, b) - t) ** 2)

    got = jax.grad(loss_gemm, argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    for g, r, name in zip(got, want, "xwb"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_maxpool2x2_matches_reduce_window():
    """reshape-max forward is bit-identical to the `reduce_window` oracle
    (the VJPs differ only in max-tie-breaking, which no engine contract
    depends on — every step path shares the reshape-max formulation)."""
    x = jax.random.normal(KEY, (3, 8, 8, 5))
    np.testing.assert_array_equal(np.asarray(maxpool2x2(x)),
                                  np.asarray(ref.maxpool2x2_ref(x)))


# ---------------------------------------------------------------------------
# 2. Bit-level twins
# ---------------------------------------------------------------------------

@given(p=st.integers(1, 2000), block_pow=st.integers(5, 9))
@settings(max_examples=12, deadline=None)
def test_sgd_update_flat_bitwise(p, block_pow):
    """Property: the flat blocked sweep produces the exact bits of the
    per-element reference for any length, including ragged tails against
    the block size (pad lanes compute 0 − lr·0 and are sliced off)."""
    ks = jax.random.split(jax.random.fold_in(KEY, p), 2)
    params = jax.random.normal(ks[0], (p,))
    grads = jax.random.normal(ks[1], (p,))
    got = sgd_update_flat(params, grads, lr=0.05, wd=0.01,
                          block_p=2 ** block_pow, interpret=True)
    # compare compiled-vs-compiled: production updates always run inside a
    # jitted program, where XLA contracts mul+add chains into FMAs — the
    # eager reference rounds each op separately and can differ by 1 ULP
    want = jax.jit(lambda p, g: ref.sgd_update_ref(p, g, lr=0.05,
                                                   wd=0.01))(params, grads)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sgd_update_tree_matches_optimizer(use_pallas):
    """Both `sgd_update_tree` branches (per-leaf jnp and flatten-concat
    kernel sweep) return the exact bits of `optimizers.sgd` — the update
    is elementwise, so neither flattening nor blocking can reassociate."""
    from repro.optim import make_optimizer
    ks = jax.random.split(KEY, 4)
    params = {"c1": {"w": jax.random.normal(ks[0], (3, 3, 3, 4)),
                     "b": jnp.zeros((4,))},
              "fc": {"w": jax.random.normal(ks[1], (64, 10)),
                     "b": 0.1 * jax.random.normal(ks[2], (10,))}}
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(ks[3], p.size),
                                    p.shape), params)
    opt = make_optimizer("sgd", 0.05, 0.01)
    # jitted like every production update (FMA contraction, see above)
    want, _ = jax.jit(opt.update)(params, grads, opt.init(params), 0)
    got = jax.jit(lambda p, g: sgd_update_tree(
        p, g, lr=0.05, wd=0.01, use_pallas=use_pallas,
        interpret=True))(params, grads)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tiny_cnn():
    from repro.configs import get_arch
    from repro.models import build_model
    cfg = dataclasses.replace(get_arch("paper-cnn"), d_model=4, d_ff=32)
    return build_model(cfg)


def test_cnn_attaches_fused_loss_twin():
    """build_cnn registers the scan-safe twin under FUSED_LOSS_ATTR and
    the capability probe resolves it; loss functions without the attribute
    (every matmul model) probe to themselves."""
    model = _tiny_cnn()
    twin = getattr(model.loss_fn, FUSED_LOSS_ATTR)
    assert fused_loss_for(model.loss_fn) is twin

    def plain_loss(p, b):
        return 0.0
    assert fused_loss_for(plain_loss) is plain_loss

    # the twin agrees with the native lax.conv loss to f32 tolerance
    params = model.init(KEY)
    batch = {"images": jax.random.normal(KEY, (4, 32, 32, 3)),
             "labels": jnp.arange(4) % 10}
    np.testing.assert_allclose(float(twin(params, batch)),
                               float(model.loss_fn(params, batch)),
                               rtol=1e-4, atol=1e-5)


def test_zero_alpha_beta_pool_step_is_plain_step():
    """α = 0, β = 0 degenerates the regularized pool step to the plain
    step bit-for-bit on the tiny CNN: the reg terms multiply to exact
    zeros, and adding exact zero to the task grads changes no bits."""
    from repro.api import LocalTrainer
    from repro.configs import FedConfig
    from repro.core import ModelPool
    model = _tiny_cnn()
    fed = FedConfig(n_clients=2, pool_size=2, e_local=2, e_warmup=1,
                    learning_rate=1e-2, alpha=0.0, beta=0.0,
                    optimizer="sgd")
    trainer = LocalTrainer(model.loss_fn, fed)
    anchor = model.init(KEY)
    live = jax.tree.map(lambda x: x + 0.05, anchor)   # ≠ anchor: finite
    pool = ModelPool.create(anchor, capacity=fed.pool_size + 1)
    pool = pool.append(jax.tree.map(lambda x: x * 0.9, anchor))
    batch = {"images": jax.random.normal(KEY, (8, 32, 32, 3)),
             "labels": jnp.arange(8) % 10}
    opt = trainer.opt

    def fresh():
        p = jax.tree.map(jnp.array, live)
        return p, opt.init(p)

    p_pool, _, t_pool = trainer.pool_step(*fresh(), batch, pool, 0)
    p_plain, _, t_plain = trainer.plain_step(*fresh(), batch, 0)
    assert float(t_pool) == float(t_plain)
    for a, b in zip(jax.tree.leaves(p_pool), jax.tree.leaves(p_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 3. Engine bit-identity on the conv model (the carve-out deletion proof)
# ---------------------------------------------------------------------------

FED_CNN = None  # built lazily: FedConfig import kept local to helpers


def _cnn_fed():
    from repro.configs import FedConfig
    return FedConfig(n_clients=2, pool_size=2, e_local=2, e_warmup=1,
                     learning_rate=1e-2)


def _cnn_data(n=96):
    from repro.data import dirichlet_partition, make_image_dataset
    ds = make_image_dataset(n_samples=n, seed=0, noise=2.0)
    parts = dirichlet_partition(ds.labels, 2, 0.5, seed=0)
    return [{"images": ds.images[p], "labels": ds.labels[p]} for p in parts]


def _cnn_iters(data, base=0):
    from repro.data import batch_iterator
    return [batch_iterator(c, 8, seed=base * 100 + i)
            for i, c in enumerate(data)]


def _cnn_plans(data, base=0):
    from repro.data import DataPlan
    return [DataPlan(c, 8, seed=base * 100 + i)
            for i, c in enumerate(data)]


def _assert_trees_bitwise_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


def test_cnn_scanned_bit_identical_to_per_step_sequential():
    """The acceptance contract that deleted the carve-out: the paper CNN
    (tiny widths) on DataPlans — local phases scan-compiled through the
    fused GEMM loss — is bit-identical to the per-step iterator path."""
    from repro.api import Experiment, run
    model = _tiny_cnn()
    fed = _cnn_fed()
    data = _cnn_data()
    a = run(Experiment(model=model, client_iters=_cnn_iters(data), fed=fed,
                       strategy="fedelmy", key=KEY))
    b = run(Experiment(model=model, client_iters=_cnn_plans(data), fed=fed,
                       strategy="fedelmy", key=KEY))
    _assert_trees_bitwise_equal(a.params, b.params)
    if a.final_pool is not None:
        _assert_trees_bitwise_equal(a.final_pool, b.final_pool)


def test_cnn_scanned_bit_identical_batched():
    """Same contract through `run_batch`: a DataPlan-carrying CNN group
    runs its local phases as one vmapped scan (batched GEMMs, not grouped
    convs) and stays bit-identical per run to sequential iterator runs."""
    from repro.api import BatchAxes, Experiment, run, run_batch
    model = _tiny_cnn()
    fed = _cnn_fed()
    data = _cnn_data()
    seeds = [0, 1]
    seq = [run(Experiment(model=model, client_iters=_cnn_iters(data, s),
                          fed=fed, strategy="fedelmy",
                          key=jax.random.PRNGKey(s)))
           for s in seeds]
    batch = run_batch(
        Experiment(model=model, client_iters=_cnn_plans(data), fed=fed,
                   strategy="fedelmy"),
        axes=BatchAxes(seeds=seeds,
                       client_iters_for_seed=lambda s: _cnn_plans(data, s)))
    assert batch.n_compiled_groups == 1
    for s, b in zip(seq, batch):
        _assert_trees_bitwise_equal(s.params, b.params)

