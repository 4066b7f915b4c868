"""The main path's Pallas kernels compiled for a described TPU v5e.

Nothing runs: each test lowers a kernel at its real width and compiles it
with the TPU compiler for a chip that is described, not attached. That
refuses what interpret mode cannot see — unaligned slices, more VMEM than a
kernel may use, programs that do not fit the device — at no chip time.

* `bgmv_pallas` at the llama3.2-1b sites the factored forward calls it
  with: `wq` (2048→2048), `w_down` (8192→2048) and the tied unembed
  (2048→128256, the d_out the output tiling exists for);
* `matmul_blocked`, forward and custom-VJP backward, at the paper CNN's
  im2col GEMM shapes (batch 32, 32×32×3 inputs, widths 64/128/256);
* `sgd_update_flat` over the paper CNN's flattened parameter vector;
* the held experts' grouped GEMM (`ops.grouped_matmul`, the megablox
  kernel), the SwiGLU's gate, up and down and their backward, at
  DeepSeek-V2-Lite's widths and the deepseek-v2-lite.silo_train cell's
  rows (8 x 2048 tokens x top-6, 8 experts held);
* the training self-attention's splash kernels (`ops.attention`, through
  `layers.flash_attention`), forward and backward, at both cells' shapes:
  granite-8b (batch 1, 32 query heads over 8 KV heads, T 2048, head 128)
  and deepseek-v2-lite (batch 4, 16 heads, T 2048, qk 192, v 128).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bgmv import bgmv_pallas
from repro.kernels.local_step import gemm, matmul_blocked, sgd_update_flat

POOL_S, RANK = 5, 8                 # serving pool capacity, factor rank
N_ROWS = 256                        # B·T activation rows per BGMV call
CNN_BATCH = 32
CNN_PARAMS = 1_422_218              # paper CNN parameter count

# (B·H·W rows, k·k·C_in, C_out) of the three convs after im2col
CNN_GEMMS = {"conv1": (CNN_BATCH * 32 * 32, 27, 64),
             "conv2": (CNN_BATCH * 16 * 16, 576, 128),
             "conv3": (CNN_BATCH * 8 * 8, 1152, 256)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d_in,d_out", [(2048, 2048), (8192, 2048),
                                        (2048, 128256)],
                         ids=["wq", "w_down", "tied_unembed"])
def test_bgmv_compiles_at_llama_widths(one_chip, d_in, d_out):
    x = _spec((POOL_S, N_ROWS, d_in), jnp.bfloat16, one_chip)
    u = _spec((POOL_S, d_in, RANK), jnp.float32, one_chip)
    v = _spec((POOL_S, d_out, RANK), jnp.float32, one_chip)
    _assert_mosaic(jax.jit(bgmv_pallas).lower(x, u, v).compile())


@pytest.mark.parametrize("layer", sorted(CNN_GEMMS))
def test_matmul_blocked_forward_compiles(one_chip, layer):
    m, k, n = CNN_GEMMS[layer]
    a = _spec((m, k), jnp.float32, one_chip)
    b = _spec((k, n), jnp.float32, one_chip)
    _assert_mosaic(jax.jit(matmul_blocked).lower(a, b).compile())


@pytest.mark.parametrize("layer", sorted(CNN_GEMMS))
def test_matmul_blocked_vjp_compiles(one_chip, layer):
    """The custom VJP runs the same blocked kernel for dA and dB."""
    m, k, n = CNN_GEMMS[layer]
    a = _spec((m, k), jnp.float32, one_chip)
    b = _spec((k, n), jnp.float32, one_chip)

    def loss(a, b):
        return gemm(a, b, use_pallas=True).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(a, b).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_sgd_update_flat_compiles(one_chip):
    p = _spec((CNN_PARAMS,), jnp.float32, one_chip)

    def step(p, g):
        return sgd_update_flat(p, g, lr=1e-3, wd=1e-4)

    _assert_mosaic(jax.jit(step).lower(p, p).compile())


def test_grouped_matmul_swiglu_and_vjp_compile_at_deepseek_widths(
        one_chip, monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    rows, d, f, held = 8 * 2048 * 6, 2048, 1408, 8
    x = _spec((rows, d), jnp.bfloat16, one_chip)
    w_in = _spec((held, d, f), jnp.bfloat16, one_chip)
    w_out = _spec((held, f, d), jnp.bfloat16, one_chip)
    sizes = _spec((held,), jnp.int32, one_chip)

    def loss(x, wg, wu, wd, sizes):
        g = ops.grouped_matmul(x, wg, sizes)
        u = ops.grouped_matmul(x, wu, sizes)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = ops.grouped_matmul(h, wd, sizes, x.dtype).astype(jnp.float32)
        return (y * y).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        x, w_in, w_in, w_out, sizes).compile()
    # forward 3, input gradients 3, weight gradients 3
    assert compiled.as_text().count("tpu_custom_call") >= 9


# B, T, query heads, KV heads, qk dim, v dim, scale
ATTN_CELLS = {"granite-8b": (1, 2048, 32, 8, 128, 128, None),
              "deepseek-v2-lite": (4, 2048, 16, 16, 192, 128, 0.1147)}


@pytest.mark.parametrize("cell", sorted(ATTN_CELLS))
def test_attention_kernel_and_vjp_compile_at_the_cells_shapes(
        one_chip, monkeypatch, cell):
    from repro.kernels import ops
    from repro.models import layers as L
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    b, t, h, kv, hd, vd, scale = ATTN_CELLS[cell]
    q = _spec((b, t, h, hd), jnp.bfloat16, one_chip)
    k = _spec((b, t, kv, hd), jnp.bfloat16, one_chip)
    v = _spec((b, t, kv, vd), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        o = L.flash_attention(q, k, v, causal=True, scale=scale)
        return (o.astype(jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile()
    # forward, dq and dkv, each printed on one line with its op's name,
    # so that a line-wise reader of the program finds the kernel's scope
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) >= 3
    assert all(re.search(r'op_name="[^"]*attn\.kernel', line)
               for line in calls)
