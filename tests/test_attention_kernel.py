"""The training self-attention's TPU kernel (`kernels/ops.attention`, jax's
splash attention) against the jnp KV-block scan that every other call and
the CPU take, and the dispatch between them in
`models/layers.flash_attention`.

On the CPU the kernel runs in interpret mode, its body as jax ops, so
the comparisons keep to T <= 256. The dispatch is judged from shapes,
dtypes and the configured precision alone; `ops._use_pallas` stands in
for the TPU.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.kernels import ops
from repro.models import layers as L

# Both sides round their output to bf16; the kernel's sums run in another
# order than the scan's. So they agree to two bf16 ulps of the largest
# value (2 x 2^-8 relative).
ULPS = 2 * 2.0 ** -8

CASES = {           # B, T, H, KV, qk dim, v dim, causal, scale
    "gqa": (1, 256, 4, 2, 128, 128, True, None),
    "mla": (1, 256, 2, 2, 192, 128, True, 0.1),
    "full_mask": (1, 256, 4, 2, 128, 128, False, None),
}


def _inputs(b, t, h, kv, hd, vd, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, t, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kv, vd)).astype(dtype)
    w = jax.random.normal(ks[3], (b, t, h, vd))
    return q, k, v, w


def _out_and_grads(attn, q, k, v, w):
    def loss(q, k, v):
        return (attn(q, k, v).astype(jnp.float32) * w).sum()
    out = jax.jit(attn)(q, k, v)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return (out,) + tuple(grads)


def _assert_close(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    bound = ULPS * float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= bound


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_scan_forward_and_grads(case):
    b, t, h, kv, hd, vd, causal, scale = CASES[case]
    q, k, v, w = _inputs(b, t, h, kv, hd, vd)
    assert not ops.attention_fits(q, k, v, window=0, q_offset=0)  # CPU

    def kernel(q, k, v):
        return ops.attention(q, k, v, causal=causal, scale=scale)

    def scan(q, k, v):
        return L.flash_attention(q, k, v, causal=causal, scale=scale)

    got = _out_and_grads(kernel, q, k, v, w)
    want = _out_and_grads(scan, q, k, v, w)
    assert got[0].shape == (b, t, h, vd) and got[0].dtype == jnp.bfloat16
    for g, x in zip(got[1:], (q, k, v)):
        assert g.shape == x.shape
    for g, r in zip(got, want):
        _assert_close(g, r)


def _shape(b, t, h, kv, hd, vd, dtype=jnp.bfloat16, tq=None):
    return (jax.ShapeDtypeStruct((b, tq or t, h, hd), dtype),
            jax.ShapeDtypeStruct((b, t, kv, hd), dtype),
            jax.ShapeDtypeStruct((b, t, kv, vd), dtype))


GRANITE = dict(b=1, t=2048, h=32, kv=8, hd=128, vd=128)
DEEPSEEK = dict(b=4, t=2048, h=16, kv=16, hd=192, vd=128)
DISPATCH = {        # shapes, window, q_offset, precision -> takes kernel
    "granite_cell": (_shape(**GRANITE), 0, 0, None, True),
    "deepseek_cell": (_shape(**DEEPSEEK), 0, 0, None, True),
    "long_window": (_shape(**GRANITE), 4096, 0, None, True),
    "bfloat16_precision": (_shape(**GRANITE), 0, 0, "bfloat16", True),
    "q_offset": (_shape(**GRANITE), 0, 128, None, False),
    "tq_ne_tk": (_shape(**GRANITE, tq=1024), 0, 0, None, False),
    "short_window": (_shape(**GRANITE), 1024, 0, None, False),
    "f32_inputs": (_shape(**GRANITE, dtype=jnp.float32), 0, 0, None, False),
    "highest_precision": (_shape(**GRANITE), 0, 0, "highest", False),
    "unblocked_seq": (_shape(**dict(GRANITE, t=2000)), 0, 0, None, False),
}


@pytest.mark.parametrize("case", sorted(DISPATCH))
def test_dispatch_is_judged_from_the_call(case, monkeypatch):
    (q, k, v), window, q_offset, precision, kernel = DISPATCH[case]
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    with jax.default_matmul_precision(precision):
        assert ops.attention_fits(q, k, v, window=window,
                                  q_offset=q_offset) is kernel


def test_no_kernel_off_tpu():
    q, k, v = _shape(**GRANITE)
    assert not ops.attention_fits(q, k, v, window=0, q_offset=0)


def test_flash_attention_routes_by_the_dispatch(monkeypatch):
    """A call the kernel fits goes to `ops.attention`."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    took = []

    def spy(name):
        def f(q, k, v, **kw):
            took.append(name)
            return jnp.zeros(q.shape[:3] + v.shape[3:], q.dtype)
        return f

    monkeypatch.setattr(ops, "attention", spy("kernel"))
    monkeypatch.setattr(L, "_scan_attention", spy("scan"))
    q, k, v, _ = _inputs(1, 128, 2, 2, 64, 64)
    # a fresh function each time: jax caches a trace by the function
    jax.eval_shape(lambda q, k, v: L.flash_attention(q, k, v), q, k, v)
    assert took == ["kernel"]


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "scan"])
def test_compiled_attention_names_its_path(kernel, monkeypatch):
    """`flash_attention` runs under `attn.kernel` or `attn.jnp`, so the
    compiled step says which path it took (the kernel here in interpret
    mode, as on the chip it would be a Mosaic call)."""
    if kernel:
        monkeypatch.setattr(ops, "_use_pallas", lambda: True)
        monkeypatch.setattr(ops, "_interpret", lambda: True)
    q, k, v, _ = _inputs(1, 128, 2, 2, 128, 128)
    text = jax.jit(lambda q, k, v: L.flash_attention(q, k, v)).lower(
        q, k, v).compile().as_text()
    took, other = ((obs.ATTN_KERNEL, obs.ATTN_JNP) if kernel
                   else (obs.ATTN_JNP, obs.ATTN_KERNEL))
    assert f"/{took}/" in text and f"/{other}/" not in text


@pytest.mark.parametrize("name", ["granite-8b", "deepseek-v2-lite-16b"])
def test_cells_decoders_take_the_kernel_in_every_layer(name, monkeypatch):
    """The two cells' decoders at a reduced size, in bf16 at a sequence of
    one kernel block: on a TPU their loss and its gradient call the kernel
    from every layer's attention and never the scan."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import build_model
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    took = []

    def spy(label, fn):
        def f(q, k, v, **kw):
            took.append(label)
            return fn(q, k, v, **kw)
        return f

    monkeypatch.setattr(ops, "attention", spy("kernel", ops.attention))
    monkeypatch.setattr(L, "_scan_attention",
                        spy("scan", L._scan_attention))
    cfg = dataclasses.replace(get_arch(name).reduced(),
                              param_dtype="bfloat16")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, ops.ATTN_BLOCKS[-1]), jnp.int32)
    jax.eval_shape(jax.grad(model.loss_fn), params,
                   {"tokens": tokens, "labels": tokens})
    assert took and set(took) == {"kernel"}
