"""The engine's batch-axis placement and local meshes (single-device
versions)."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_batch_mesh
from repro.sharding.specs import run_batch_specs


def _fake_mesh():
    """An abstract 256-device mesh for spec construction only (specs are
    pure metadata — no devices touched)."""
    class _FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    return _FakeMesh()


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
