"""The decoder layer's checkpoint (`transformer.decoder_remat`): it keeps
the projection outputs for the backward where their bytes fit in a share
of the device's memory, and is the plain checkpoint otherwise.

1. Keeping them changes no number: the loss and its gradient match the
   plain checkpoint's (dense, MoE, MoE with MLA).
2. The rule: keep where the reckoned bytes fit under `KEEP_PROJ_SHARE` of
   the device's `bytes_limit`, never where no memory is reported, as on
   the CPU, where the step is the plain checkpoint's.
3. The bytes reckoned are the residuals JAX itself keeps beyond the plain
   checkpoint's.
"""
import contextlib
import dataclasses
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from repro import obs
from repro.configs import get_arch
from repro.models import build_model
from repro.models import transformer as TR

B, T = 2, 32
ARCHS = ["granite-8b", "qwen3-moe-235b-a22b", "deepseek-v2-lite-16b"]


def _tiny(name, dtype="float32"):
    return dataclasses.replace(get_arch(name).reduced(), param_dtype=dtype)


def _batch(cfg, seed=0):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}


@contextlib.contextmanager
def _device_limit(monkeypatch, limit):
    with monkeypatch.context() as m:
        m.setattr(TR, "device_bytes_limit", lambda: limit)
        yield


KEEP, PLAIN = 1 << 40, None


@pytest.mark.parametrize("name", ARCHS)
def test_keeping_projections_matches_plain_checkpoint(name, monkeypatch):
    cfg = _tiny(name)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = _batch(cfg)
    out = {}
    for label, limit in (("plain", PLAIN), ("keep", KEEP)):
        with _device_limit(monkeypatch, limit):
            fn = jax.jit(jax.value_and_grad(model.loss_fn))
            text = fn.lower(params, batch).as_text(debug_info=True)
            out[label] = fn(params, batch)
        assert (obs.REMAT_KEEP in text) == (label == "keep")
    (l_p, g_p), (l_k, g_k) = out["plain"], out["keep"]
    np.testing.assert_allclose(float(l_k), float(l_p), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_keeps_only_what_fits_in_reported_memory():
    n = 1000
    limit = int(n / TR.KEEP_PROJ_SHARE)
    assert TR.keeps_proj(n, limit)
    assert not TR.keeps_proj(n, limit - 4)
    assert not TR.keeps_proj(n, None)
    assert not TR.keeps_proj(0, None)


def test_cpu_reports_no_memory_so_the_checkpoint_is_plain():
    cfg = get_arch("granite-8b")
    assert TR.device_bytes_limit() is None
    assert TR.decoder_remat(cfg, 1, 2048, jnp.bfloat16) is jax.checkpoint


def test_granite_bytes_at_the_cells_shapes():
    # 3 layers x 2048 tokens x (bf16 q, k, v, o: 4096 + 2 x 1024 + 4096;
    # f32 gate, up: 2 x 14336).
    cfg = dataclasses.replace(get_arch("granite-8b"), n_layers=3)
    per_token = 2 * (4096 + 2 * 1024 + 4096) + 4 * 2 * 14336
    assert TR.kept_proj_bytes(cfg, 1, 2048, jnp.bfloat16) == \
        3 * 2048 * per_token == 830472192


_DTYPES = {"f32": 4, "bf16": 2, "i32": 4, "bool": 1}


def _residual_bytes(fn, *args) -> int:
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        print_saved_residuals(fn, *args)
    total = 0
    for line in buf.getvalue().splitlines():
        m = re.match(r"(\w+)\[([\d,]*)\]", line)
        assert m, line
        total += _DTYPES[m.group(1)] * int(np.prod(
            [int(d) for d in m.group(2).split(",") if d]))
    return total


@pytest.mark.parametrize("name", ARCHS)
def test_reckoned_bytes_are_the_residuals_jax_keeps(name, monkeypatch):
    cfg = _tiny(name, "bfloat16")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((B, T), jnp.int32)
             for k in ("tokens", "labels")}
    with _device_limit(monkeypatch, PLAIN):
        plain = _residual_bytes(model.loss_fn, params, batch)
    with _device_limit(monkeypatch, KEEP):
        keep = _residual_bytes(model.loss_fn, params, batch)
    assert keep - plain == TR.kept_proj_bytes(cfg, B, T, jnp.bfloat16)
