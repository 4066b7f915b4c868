"""The expert layer for one device's share of expert parallelism
(`models/moe.py`), YaRN MLA (`models/layers.py`) and the decoder's leading
dense layers (`models/transformer.py`), against the plain reference
(`bench/reference/mla_moe.py`) and against the formulas, on the CPU at a
small size in f32.

* Share-sum: the parts that all E / H shares of the experts compute, with
  the shared experts and the balance loss counted once, add up to the
  uncut reference's layer.
* Dropless: the layer equals a dense all-expert einsum weighted by the
  gates, also under a router that sends every token to the same experts.
* The held path's row ladder (`held_rungs`, `held_capacity`), and the
  compacted layer against the dense einsum, output and gradient, at each
  rung: a random router, one biased past the first rung, and every token
  on held experts; one rung and no `switch` where every expert is held.
* The gradient of a checkpointed layer, lowered: no array of N*k rows
  outside the last rung's branch.
* The gates with and without `norm_topk_prob`.
* YaRN's frequencies, mscale and softmax scale against the published
  formula at DeepSeek-V2-Lite's values.
* The leading dense layer's parameter tree and `kept_proj_bytes` at the
  cell's shapes; the routing counter against the layer's own group sizes.
* The system's loss and gradient against the reference on seeded weights.
* The TPU route of the grouped GEMM (the megablox kernel, interpreted)
  against its jnp twin.
"""
import dataclasses
import functools
import math
import re
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

from bench.reference import mla_moe as R  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models import transformer as TR  # noqa: E402

E, K, HELD, D, F = 8, 3, 4, 32, 16


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _cfg(n_experts=E, top_k=K, held=HELD, **moe):
    base = get_arch("deepseek-v2-lite-16b").reduced()
    return dataclasses.replace(
        base, param_dtype="float32", n_layers=3, experts_held=held,
        moe=dataclasses.replace(base.moe, n_experts=n_experts, top_k=top_k,
                                n_shared_experts=2, **moe))


def _ref_cfg(cfg):
    """The reference's configuration keys for an `ArchConfig`."""
    m, y, moe = cfg.mla, cfg.yarn, cfg.moe
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": m.qk_nope_dim,
            "qk_rope_head_dim": m.qk_rope_dim,
            "kv_lora_rank": m.kv_lora_rank, "v_head_dim": m.v_head_dim,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": {
                "factor": y.factor, "beta_fast": y.beta_fast,
                "beta_slow": y.beta_slow, "mscale": y.mscale,
                "mscale_all_dim": y.mscale_all_dim,
                "original_max_position_embeddings": y.original_max_position},
            "router_outputs": moe.n_experts, "num_experts_per_tok": moe.top_k,
            "norm_topk_prob": moe.norm_topk_prob,
            "routed_scaling_factor": moe.routed_scaling,
            "aux_loss_alpha": moe.aux_loss_alpha}


def _layer_params(key, n_experts=E, d=D, f=F):
    ks = jax.random.split(key, 7)
    n = lambda k, s, fan: jax.random.normal(k, s) / math.sqrt(fan)  # noqa
    return {"router": n(ks[0], (d, n_experts), d),
            "w_gate": n(ks[1], (n_experts, d, f), d),
            "w_up": n(ks[2], (n_experts, d, f), d),
            "w_down": n(ks[3], (n_experts, f, d), f),
            "shared": {"w_gate": n(ks[4], (d, 2 * f), d),
                       "w_up": n(ks[5], (d, 2 * f), d),
                       "w_down": n(ks[6], (2 * f, d), 2 * f)}}


def _moe_cfg(**moe):
    cfg = _cfg(**moe)
    return dataclasses.replace(cfg, d_model=D, moe=dataclasses.replace(
        cfg.moe, d_ff_expert=F))


def _x(key, b=2, t=24):
    return jax.random.normal(key, (b, t, D))


def _share(p, first, held):
    """The weights of the device that holds experts [first, first + held):
    its experts, and the router with their columns rolled to the front."""
    return {**p, "router": jnp.roll(p["router"], -first, axis=1),
            **{n: p[n][first:first + held]
               for n in ("w_gate", "w_up", "w_down")}}


def test_shares_add_up_to_the_uncut_layer():
    cfg = _moe_cfg()
    p, x = _layer_params(jax.random.PRNGKey(0)), _x(jax.random.PRNGKey(1))
    want, want_aux = R._moe(_ref_cfg(cfg), p, x, None)
    shared = L.mlp(p["shared"], x)
    total = shared
    for j in range(E // HELD):
        y, aux, sizes = MOE.moe_ffn(_share(p, j * HELD, HELD), cfg, x)
        total = total + (y - shared)
        np.testing.assert_allclose(float(aux), float(want_aux), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _dense_moe(p, cfg, x):
    """Every held expert on every token, weighted by the gate of the tokens
    that chose it; the shared experts added."""
    gates, idx, _ = MOE.route(p["router"], cfg, x)
    w = jnp.sum(jax.nn.one_hot(idx, cfg.moe.n_experts) * gates[..., None],
                1)[:, :p["w_gate"].shape[0]]                    # (N, held)
    xf = x.reshape(-1, x.shape[-1])
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", xf, p["w_gate"])) * \
        jnp.einsum("nd,edf->enf", xf, p["w_up"])
    y = jnp.einsum("enf,efd,ne->nd", h, p["w_down"], w)
    return y.reshape(x.shape) + L.mlp(p["shared"], x)


@pytest.mark.parametrize("skew", [False, True], ids=["random", "skewed"])
def test_dropless_equals_dense_all_expert_einsum(skew):
    cfg = _moe_cfg(held=0)
    p, x = _layer_params(jax.random.PRNGKey(2)), _x(jax.random.PRNGKey(3))
    if skew:        # every token picks experts 0..k-1: no capacity holds it
        x = x + 20.0
        p["router"] = p["router"].at[:, :K].add(
            jnp.linspace(1.0, 0.5, K)[None, :])
        _, idx, _ = MOE.route(p["router"], cfg, x)
        assert set(np.unique(np.asarray(idx))) == set(range(K))
    y, _, sizes = MOE.moe_ffn(p, cfg, x)
    assert int(sizes.sum()) == x.shape[0] * x.shape[1] * K
    np.testing.assert_allclose(np.asarray(y), np.asarray(_dense_moe(
        p, cfg, x)), rtol=1e-5, atol=1e-5)


def test_held_rungs_ladder():
    # the deepseek-v2-lite.silo_train cell: 8192 tokens, top-6, 8 of 64 held
    assert MOE.held_rungs(8192, 6, 8, 64) == (7680, 19456, 49152)
    for n, k, held, e in [(8192, 6, 8, 64), (2048, 2, 2, 8),
                          (1536, 2, 4, 16), (4096, 8, 4, 128)]:
        rungs = MOE.held_rungs(n, k, held, e)
        assert 1 < len(rungs) <= 3 and rungs[-1] == n * min(k, held)
        assert all(c % ops.GMM_TM == 0 for c in rungs)
        assert all(a < b for a, b in zip(rungs, rungs[1:]))
        assert rungs[0] >= 1.25 * n * k * held / e
        cap = functools.partial(MOE.held_capacity, n, k, held, e)
        assert cap(0) == cap(rungs[0]) == rungs[0]
        assert cap(rungs[0] + 1) == rungs[1] and cap(rungs[-1]) == rungs[-1]
        with pytest.raises(ValueError):
            cap(rungs[-1] + 1)
    for n, k, held, e in [(8192, 6, 64, 64), (48, 3, 8, 8), (48, 3, 4, 8)]:
        assert MOE.held_rungs(n, k, held, e) == (n * min(k, held),)


# The held path at more than one rung: 2048 tokens, top-2 of 8 experts, 2
# held; rungs (1536, 2560, 4096).
LADDER = dict(n_experts=8, top_k=2, held=2)


def _steered(p, x, pulls):
    """Every token's logits raised by `pulls[e]` for expert e, through a
    constant first feature."""
    x = x.at[..., 0].set(10.0)
    router = p["router"].at[0].set(0.0)
    for e, pull in enumerate(pulls):
        router = router.at[0, e].set(pull)
    return {**p, "router": router}, x


@pytest.mark.parametrize("rung,pulls", [(0, ()), (1, (1.5,)),
                                        (2, (2.0, 1.5)), (0, None)],
                         ids=["random", "middle", "last", "all_held"])
def test_compacted_layer_equals_dense_einsum_with_grads(rung, pulls):
    all_held = pulls is None
    cfg = _moe_cfg(**(dict(LADDER, held=0) if all_held else LADDER))
    held = cfg.resolved_experts_held
    p = _share(_layer_params(jax.random.PRNGKey(12)), 0, held)
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 1024, D))
    p, x = _steered(p, x, pulls or ())
    n, k = 2048, cfg.moe.top_k
    rungs = MOE.held_rungs(n, k, held, cfg.moe.n_experts)
    assert rungs == ((4096,) if all_held else (1536, 2560, 4096))
    _, _, sizes = MOE.moe_ffn(p, cfg, x)
    h = int(sizes.sum())
    assert MOE.held_capacity(n, k, held, cfg.moe.n_experts, h) == rungs[rung]
    if rung == 2:                   # dropless at the last rung
        assert h == n * min(k, held)
    ct = jax.random.normal(jax.random.PRNGKey(14), x.shape)
    panels = ("w_gate", "w_up", "w_down")

    def loss(layer):
        def f(x, *w):
            y = layer({**p, **dict(zip(panels, w))}, cfg, x)
            return jnp.sum((y[0] if isinstance(y, tuple) else y) * ct)
        return jax.grad(f, argnums=(0, 1, 2, 3))

    args = (x,) + tuple(p[n] for n in panels)
    y = MOE.moe_ffn(p, cfg, x)[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(_dense_moe(
        p, cfg, x)), rtol=1e-5, atol=1e-5)
    for a, b in zip(loss(MOE.moe_ffn)(*args), loss(_dense_moe)(*args)):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * scale
    jaxpr = str(jax.make_jaxpr(loss(MOE.moe_ffn))(*args))
    assert ("cond[" in jaxpr) == (not all_held)


def _outside_last_branch(text, rows):
    """Lines of lowered StableHLO outside the last region of every
    `stablehlo.case` (and outside private functions called only from
    there) that hold a floating-point array of `rows` rows and rank two
    or more: the buffers of the data, not the routing's int indices."""
    big = re.compile(rf"tensor<{rows}(x\d+)+x(f|bf)\d+>")
    cases, stack, lines = [], [], []     # stack: [case, region] or None
    func = None
    for line in text.splitlines():
        s = line.strip()
        m = re.match(r"func\.func (?:public |private )?@([\w.$-]+)", s)
        if m:
            func = m.group(1)
        if s == "}, {" and stack and stack[-1] is not None:
            stack[-1][1] += 1
            cases[stack[-1][0]] += 1
            continue
        net = s.count("{") - s.count("}")
        if net < 0:                     # a case's results lie outside it
            del stack[net:]
        lines.append((func, line, [f and tuple(f) for f in stack]))
        if net > 0:
            opens = [None] * net
            if '"stablehlo.case"' in s:
                cases.append(1)
                opens[-1] = [len(cases) - 1, 0]
            stack += opens

    def in_last(frames):
        return any(f is not None and f[1] == cases[f[0]] - 1
                   for f in frames)

    calls = {}                           # callee -> [(caller, in last)]
    for func, line, frames in lines:
        for callee in re.findall(r"call @([\w.$-]+)", line):
            calls.setdefault(callee, []).append((func, in_last(frames)))
    inside = set()
    while True:
        more = {f for f, sites in calls.items() if f not in inside and all(
            last or caller in inside for caller, last in sites)}
        if not more:
            break
        inside |= more
    return [line.strip() for func, line, frames in lines
            if big.search(line) and not in_last(frames)
            and func not in inside]


def test_checkpointed_layer_grad_has_no_nk_rows_outside_last_rung():
    base = get_arch("deepseek-v2-lite-16b").reduced()
    cfg = dataclasses.replace(base, experts_held=4, moe=dataclasses.replace(
        base.moe, n_experts=16, top_k=2))
    b, t, k = 3, 512, 2
    assert MOE.held_rungs(b * t, k, 4, 16) == (1024, 2048, 3072)
    p = jax.eval_shape(lambda: TR._block_init(jax.random.PRNGKey(0), cfg,
                                              jnp.float32))
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    @jax.checkpoint
    def layer(p, x):
        y, aux, _ = TR._block_fwd(p, cfg, x, positions)
        return jnp.sum(y ** 2) + aux

    x = jax.ShapeDtypeStruct((b, t, cfg.d_model), jnp.float32)
    text = jax.jit(jax.grad(layer, argnums=(0, 1))).lower(p, x).as_text()
    assert text.count('"stablehlo.case"') >= 2      # forward and backward
    assert re.search(rf"tensor<{b * t * k}x\d+xf32>", text)  # last rung
    assert _outside_last_branch(text, b * t * k) == []


@pytest.mark.parametrize("norm", [False, True], ids=["raw", "normalised"])
def test_gates_follow_norm_topk_prob(norm):
    cfg = _moe_cfg(norm_topk_prob=norm, routed_scaling=1.5)
    p, x = _layer_params(jax.random.PRNGKey(4)), _x(jax.random.PRNGKey(5))
    gates, idx, _ = MOE.route(p["router"], cfg, x)
    probs = jax.nn.softmax(x.reshape(-1, D) @ p["router"], -1)
    top = jnp.take_along_axis(probs, idx, -1)
    want = top / top.sum(-1, keepdims=True) if norm else top
    np.testing.assert_allclose(np.asarray(gates), 1.5 * np.asarray(want),
                               rtol=1e-6)
    assert np.all(np.asarray(top[:, :-1] >= top[:, 1:]))     # greedy order


def test_yarn_frequencies_and_scale_follow_the_formula():
    cfg = get_arch("deepseek-v2-lite-16b")
    y, dim, theta = cfg.yarn, cfg.mla.qk_rope_dim, cfg.rope_theta
    # correction range: floor/ceil of 64 ln(4096 / (2 pi beta)) / (2 ln 1e4)
    lo = math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                    / (2 * math.log(1e4)))
    hi = math.ceil(64 * math.log(4096 / (2 * math.pi * 1))
                   / (2 * math.log(1e4)))
    assert (lo, hi) == (10, 23)
    got = np.asarray(L.yarn_freqs(dim, theta, y))
    for i in range(dim // 2):
        f = theta ** (-2 * i / dim)
        r = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        np.testing.assert_allclose(got[i], f / 40 * r + f * (1 - r),
                                   rtol=1e-6)
    np.testing.assert_allclose(got, np.asarray(R.yarn_inv_freq(
        {"qk_rope_head_dim": dim, "rope_theta": theta,
         "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                          "original_max_position_embeddings": 4096}})),
        rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert L.yarn_mscale(y.factor, y.mscale_all_dim) == pytest.approx(mscale)
    assert L.mla_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)


def test_leading_dense_layer_tree_and_kept_bytes():
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite-16b"), n_layers=5,
                              vocab_size=12800, experts_held=8)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    dense, moe = shapes["dense_layers"]["ffn"], shapes["layers"]["ffn"]
    assert dense["w_gate"].shape == (1, 2048, 10944)
    assert dense["w_down"].shape == (1, 10944, 2048)
    assert moe["router"].shape == (4, 2048, 64)
    assert moe["w_gate"].shape == (4, 8, 2048, 1408)
    assert moe["shared"]["w_up"].shape == (4, 2048, 2816)
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == 535_060_992
    # per token: MLA's q, latent, rope key, k, v and o in bf16 (9792
    # values) in every layer; f32 gate and up of the dense layer's 10944
    # and of the shared experts' 2816 in each MoE layer
    per_token = 5 * 2 * 9792 + 4 * 2 * 10944 + 4 * 4 * 2 * 2816
    assert TR.kept_proj_bytes(cfg, 8, 2048, jnp.bfloat16) == \
        8 * 2048 * per_token == 4_515_168_256
    # a v5e's bytes_limit: batch 8 recomputes, the cell's batch 4 keeps
    limit = 16_909_336_064
    assert not TR.keeps_proj(TR.kept_proj_bytes(cfg, 8, 2048, jnp.bfloat16),
                             limit)
    assert TR.keeps_proj(TR.kept_proj_bytes(cfg, 4, 2048, jnp.bfloat16),
                         limit)


def test_route_counts_are_the_layers_group_sizes():
    cfg = dataclasses.replace(_cfg(), n_layers=1, first_k_dense=0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0,
                                cfg.vocab_size)
    counts = model.route_counts(params, {"tokens": tokens})
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(32), (2, 32))
    _, _, sizes = TR._block_fwd(lp, cfg, x, positions)
    assert counts.shape == (1, HELD)
    np.testing.assert_array_equal(np.asarray(counts[0]), np.asarray(sizes))
    assert 0 < int(sizes.sum()) <= 2 * 32 * cfg.moe.top_k


def test_system_matches_reference_on_seeded_weights():
    cfg = _cfg()
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = R.init_params(shapes, jax.random.PRNGKey(8))
    tok = jax.random.randint(jax.random.PRNGKey(9), (2, 257), 0,
                             cfg.vocab_size)
    batch = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}
    got, g_got = jax.value_and_grad(model.loss_fn)(params, batch)
    want, g_want = jax.value_and_grad(
        lambda p: R.loss(_ref_cfg(cfg), p, batch))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale


def test_megablox_route_matches_jnp_twin():
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, d, f, g = 512, 128, 256, 3
    x = jax.random.normal(jax.random.PRNGKey(10), (m, d))
    w = jax.random.normal(jax.random.PRNGKey(11), (g, d, f))
    sizes = jnp.array([100, 0, 229], jnp.int32)
    rest = jnp.array([m - 329], jnp.int32)
    got = gmm(x, w, jnp.concatenate([sizes, rest]), jnp.float32,
              (128, 128, 128), interpret=True)
    want = ops.grouped_matmul_ref(x, w, sizes, jnp.float32)
    assert float(jnp.abs(want[329:]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)
