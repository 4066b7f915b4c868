"""Per-assigned-architecture smoke tests: a REDUCED variant of the same
family (≤2 layers, d_model≤256, ≤4 experts) runs one forward + one train
step on CPU; output shapes asserted, no NaNs (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, FedConfig, get_arch
from repro.models import build_model
from repro.optim import make_optimizer

LLM_ARCHS = [a for a in ARCHS if a != "paper-cnn"]
KEY = jax.random.PRNGKey(0)
B, T = 2, 32


def _batch(cfg):
    tokens = jax.random.randint(jax.random.fold_in(KEY, 7), (B, T), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    if cfg.family == "encdec":
        batch["src_embeds"] = jax.random.normal(
            jax.random.fold_in(KEY, 8), (B, T, cfg.d_model))
    return batch


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = get_arch(name).reduced()
            model = build_model(cfg)
            params = model.init(KEY)
            cache[name] = (cfg, model, params)
        return cache[name]
    return get


@pytest.mark.parametrize("name", LLM_ARCHS)
def test_reduced_config_limits(name):
    cfg = get_arch(name).reduced()
    assert cfg.n_layers <= 2 and cfg.d_model <= 256
    if cfg.moe:
        assert cfg.moe.n_experts <= 4


@pytest.mark.parametrize("name", LLM_ARCHS)
def test_forward_shapes_and_finite(name, built):
    cfg, model, params = built(name)
    logits = jax.jit(model.forward)(params, _batch(cfg))
    assert logits.shape == (B, T, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{name}: non-finite logits"


@pytest.mark.parametrize("name", LLM_ARCHS)
def test_one_train_step_reduces_loss_and_is_finite(name, built):
    cfg, model, params = built(name)
    params = jax.tree.map(jnp.copy, params)
    batch = _batch(cfg)
    opt = make_optimizer("adam", 1e-3)
    state = opt.init(params)

    @jax.jit
    def step(p, s, b):
        loss, g = jax.value_and_grad(model.loss_fn)(p, b)
        p, s = opt.update(p, g, s, jnp.int32(0))
        return p, s, loss

    p1, state, l0 = step(params, state, batch)
    _, _, l1 = step(p1, state, batch)
    assert np.isfinite(float(l0)) and np.isfinite(float(l1))
    assert float(l1) < float(l0), f"{name}: loss did not decrease"


@pytest.mark.parametrize("name", [a for a in LLM_ARCHS])
def test_serve_roundtrip(name, built):
    """prefill(T-1) + decode(1) ≈ forward(T) at the last position."""
    cfg, model, params = built(name)
    batch = _batch(cfg)
    tokens = batch["tokens"]
    full = model.forward(params, batch)
    pre = dict(batch)
    pre["tokens"] = tokens[:, :T - 1]
    pre.pop("labels")
    logits_pre, cache = model.prefill(params, pre)
    np.testing.assert_allclose(np.asarray(logits_pre[:, 0]),
                               np.asarray(full[:, T - 2]),
                               rtol=2e-2, atol=2e-3)
    # grow cache seq axis by one slot so decode can insert position T-1
    def grow(c, k):
        if cfg.family in ("dense", "moe", "vlm"):
            return jnp.pad(c, ((0, 0), (0, 0), (0, 1)) + ((0, 0),) * (c.ndim - 3))
        if cfg.family == "encdec" and k in ("k", "v"):
            return jnp.pad(c, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0)))
        if cfg.family == "hybrid" and k.startswith("shared"):
            return jnp.pad(c, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0)))
        return c
    cache = {k: grow(v, k) for k, v in cache.items()}
    logits_dec, new_cache = model.decode(params, tokens[:, T - 1:T], cache,
                                         jnp.int32(T - 1))
    np.testing.assert_allclose(np.asarray(logits_dec[:, 0]),
                               np.asarray(full[:, T - 1]),
                               rtol=2e-2, atol=2e-3)
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


def test_sliding_window_ring_buffer_matches_full_context():
    """llama3.2-1b reduced has window=64 > T, so ring decode == full decode."""
    cfg = get_arch("llama3.2-1b").reduced()
    assert cfg.sliding_window == 64
    model = build_model(cfg)
    params = model.init(KEY)
    tokens = jax.random.randint(KEY, (1, 16), 0, cfg.vocab_size)
    full = model.forward(params, {"tokens": tokens})
    logits, cache = model.prefill(params, {"tokens": tokens[:, :15]})
    cache = jax.tree.map(
        lambda c: jnp.pad(c, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))), cache)
    dec, _ = model.decode(params, tokens[:, 15:16], cache, jnp.int32(15))
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, 15]),
                               rtol=1e-3, atol=1e-4)


def test_paper_cnn_smoke():
    cfg = get_arch("paper-cnn")
    model = build_model(cfg)
    params = model.init(KEY)
    batch = {"images": jax.random.normal(KEY, (4, 32, 32, 3)),
             "labels": jnp.zeros((4,), jnp.int32)}
    logits = model.forward(params, batch)
    assert logits.shape == (4, 10)
    loss = model.loss_fn(params, batch)
    assert np.isfinite(float(loss))
