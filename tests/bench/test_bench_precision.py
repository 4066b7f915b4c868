"""The precision a configuration states, as the harness applies it, and the
references' products in each precision a check asks for."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import harness  # noqa: E402
from bench.reference.products import product  # noqa: E402


def _dot(a, b, prec):
    import jax.numpy as jnp
    return jnp.dot(a, b, precision=prec)


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(3)
    return (rng.standard_normal((64, 96)).astype(np.float32),
            rng.standard_normal((96, 32)).astype(np.float32))


def test_three_passes_lie_between_one_and_exact(operands):
    a, b = operands
    exact = a.astype(np.float64) @ b.astype(np.float64)
    err = {m: float(np.max(np.abs(np.asarray(product(_dot, a, b, m))
                                  - exact)))
           for m in (None, "bfloat16_3x", "bfloat16")}
    assert err[None] < 1e-4
    assert err[None] < err["bfloat16_3x"] < err["bfloat16"] / 30


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
def test_a_dtype_rounds_both_operands(operands, dtype):
    import jax.numpy as jnp
    a, b = operands
    rounded = [jnp.asarray(x).astype(dtype).astype(jnp.float32)
               for x in (a, b)]
    want = np.asarray(rounded[0], np.float64) @ np.asarray(rounded[1],
                                                           np.float64)
    np.testing.assert_allclose(np.asarray(product(_dot, a, b, dtype)), want,
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("config,want", [
    ({"matmul_precision": "highest"}, "highest"),
    ({}, None)], ids=["stated", "absent"])
def test_the_configuration_sets_the_default_precision(config, want):
    import jax
    harness.use_precision(config)
    try:
        assert jax.config.jax_default_matmul_precision == want
    finally:
        harness.use_precision({})


def test_the_cnn_configuration_states_highest_with_its_control():
    cfg = harness.load_json(harness.BENCH / "configs" / "paper-cnn.json")
    assert cfg["param_dtype"] == "float32"
    assert cfg["matmul_precision"] == "highest"
    assert cfg["control"] == "bfloat16_3x"
