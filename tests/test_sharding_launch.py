"""Sharding rules + launch plumbing tests (single-device versions; the real
256/512-chip lowering is exercised by launch/dryrun.py — see
EXPERIMENTS.md §Dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_arch
from repro.launch import steps as S
from repro.launch.mesh import make_batch_mesh, make_local_mesh
from repro.sharding import batch_specs, cache_specs, param_specs
from repro.sharding.specs import run_batch_specs


def _fake_mesh():
    """An abstract 256-device mesh for spec construction only (specs are
    pure metadata — no devices touched)."""
    import numpy as np
    devs = np.empty((16, 16), dtype=object)

    class _FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    return _FakeMesh()


def test_param_specs_shard_big_matrices():
    cfg = get_arch("qwen2-7b")
    shapes = S.param_specs_for(cfg)
    specs = param_specs(shapes, _fake_mesh())
    flat = {"/".join(str(getattr(p, "key", p)) for p in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    wq = next(v for k, v in flat.items() if k.endswith("attn/wq"))
    assert "model" in jax.tree.leaves(wq) or "model" in tuple(wq), wq
    # stacked layer axis (leading) must never be sharded
    assert wq[0] is None
    norm = next(v for k, v in flat.items() if "final_norm" in k)
    assert all(a is None for a in norm)


@pytest.mark.parametrize("arch", ["qwen3-moe-235b-a22b",
                                  "deepseek-v2-lite-16b"])
def test_moe_expert_axis_is_expert_parallel(arch):
    cfg = get_arch(arch)
    shapes = S.param_specs_for(cfg)
    specs = param_specs(shapes, _fake_mesh())
    flat = {"/".join(str(getattr(p, "key", p)) for p in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    w_gate = flat["layers/ffn/w_gate"]
    # (L, E, d, f): expert axis sharded over model
    assert w_gate[1] == "model" and w_gate[0] is None
    # a leading dense layer's stacked FFN (L, d, f) is no expert stack: its
    # layer axis stays whole and its width takes the model axis
    for key, spec in flat.items():
        if key.startswith("dense_layers/ffn/"):
            assert spec[0] is None and "model" in tuple(spec), (key, spec)


def test_batch_specs_data_parallel():
    shapes = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
    spec = batch_specs(shapes, _fake_mesh())
    assert spec["tokens"][0] == "data"


def test_run_batch_specs_shard_run_axis_over_data():
    """The run_batch batch-axis rule: leading run axis over the data axes
    when divisible, replicate otherwise (never touch inner dims)."""
    shapes = {"w": jax.ShapeDtypeStruct((32, 128, 64), jnp.float32),
              "b": jax.ShapeDtypeStruct((32, 64), jnp.float32),
              "scalar": jax.ShapeDtypeStruct((), jnp.float32)}
    specs = run_batch_specs(shapes, _fake_mesh())
    assert specs["w"][0] == "data" and specs["w"][1:] == (None, None)
    assert specs["b"][0] == "data" and specs["b"][1] is None
    assert specs["scalar"] == P()
    # indivisible run count replicates rather than crashing
    ragged = {"w": jax.ShapeDtypeStruct((3, 8), jnp.float32)}
    assert run_batch_specs(ragged, _fake_mesh())["w"] == P(None, None)


def test_make_batch_mesh_divides_run_count():
    mesh = make_batch_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["model"] == 1
    # n_runs clipping: data axis must divide the run count
    n = make_batch_mesh(n_runs=7).shape["data"]
    assert 7 % n == 0


def test_cache_specs_seq_sharded():
    cfg = get_arch("qwen2-7b")
    shape = INPUT_SHAPES["decode_32k"]
    shapes = S.cache_specs_for(cfg, shape)
    specs = cache_specs(shapes, _fake_mesh())
    k = specs["k"]                         # (L, B, S, KV, hd)
    assert k[1] == "data" and k[2] == "model"


@pytest.mark.parametrize("arch", ["llama3.2-1b", "rwkv6-7b",
                                  "deepseek-v2-lite-16b"])
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_input_specs_are_abstract(arch, shape):
    cfg = get_arch(arch)
    specs = S.input_specs(cfg, INPUT_SHAPES[shape])
    for leaf in jax.tree.leaves(specs):
        assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_long500k_carveout():
    ok, why = S.shape_supported(get_arch("qwen2-72b"),
                                INPUT_SHAPES["long_500k"])
    assert not ok and "sub-quadratic" in why
    for a in ("rwkv6-7b", "zamba2-7b", "llama3.2-1b"):
        ok, _ = S.shape_supported(get_arch(a), INPUT_SHAPES["long_500k"])
        assert ok, a


def test_reduced_train_step_runs_on_local_mesh():
    """The exact train_step the dry-run lowers, executed for real at reduced
    scale on the local 1-device mesh."""
    import dataclasses
    cfg = get_arch("llama3.2-1b").reduced()
    shape = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=64,
                                global_batch=2)
    step = S.make_step(cfg, shape)
    specs = S.input_specs(cfg, shape)
    vals = jax.tree.map(
        lambda s: (jnp.zeros(s.shape, s.dtype)
                   if s.dtype != jnp.int32 else
                   jnp.ones(s.shape, jnp.int32)), specs)
    mesh = make_local_mesh()
    with mesh:
        params, opt_state, task = jax.jit(step)(**vals)
    assert np.isfinite(float(task))
    assert jax.tree.structure(params) == jax.tree.structure(specs["params"])


def test_serve_step_runs_reduced():
    import dataclasses
    cfg = get_arch("rwkv6-7b").reduced()
    shape = dataclasses.replace(INPUT_SHAPES["decode_32k"], seq_len=64,
                                global_batch=2)
    step = S.make_step(cfg, shape)
    specs = S.input_specs(cfg, shape)
    vals = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
    logits, cache = jax.jit(step)(**vals)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
