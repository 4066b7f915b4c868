"""Placement of the engine's batched runs on a mesh.

A leading run (or flattened run×client) axis shards over the mesh data
axes, ("pod", "data"); everything else replicates. Per-run math never
crosses that axis, so no collectives are introduced.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def data_axis_size(mesh: Mesh) -> int:
    """Total device count across the mesh data axes — the shard count a
    leading run×client axis divides into under `shard_map_flat`."""
    return _axsize(mesh, dp_axes(mesh))


def flat_axis_spec(mesh: Mesh) -> P:
    """PartitionSpec placing a leading flattened run×client axis over the
    mesh data axes (prefix form: applies to every leaf of a pytree arg)."""
    dp = dp_axes(mesh)
    return P(dp if len(dp) > 1 else dp[0])


def can_shard_flat(mesh: Optional[Mesh], n_flat: int) -> bool:
    """True when a flat batch of `n_flat` runs×clients can go under
    `shard_map_flat` on `mesh`: every device must take an equal slice
    (shard_map requires exact divisibility; indivisible batches fall back
    to the single-program vmap path)."""
    if mesh is None:
        return False
    n = data_axis_size(mesh)
    return n >= 1 and n_flat % n == 0


def shard_map_flat(fn: Callable, mesh: Mesh,
                   leading: Sequence[bool]) -> Callable:
    """Put a vmapped program under `jax.shard_map` across the mesh data
    axes. `fn` is a function whose arguments flagged True in `leading`
    carry a leading flattened run×client axis (False ⇒ replicated scalars,
    e.g. the step counter) and whose *every* output carries that axis.
    Each device then advances its slice of the batch in one compiled
    program; per-run math never crosses the axis, so no collectives are
    introduced and per-run results are bit-identical to the plain vmap
    path (pinned in tests/test_fleet.py on a 1-device mesh)."""
    spec = flat_axis_spec(mesh)
    in_specs = tuple(spec if lead else P() for lead in leading)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=spec,
                         check_vma=False)


def _axsize(mesh, axes):
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[a]
    return n


def run_batch_specs(stacked_shapes: Any, mesh: Mesh) -> Any:
    """Specs for `repro.api.run_batch` stacked pytrees: the leading *run*
    axis shards over the mesh data axes (each device advances its slice of
    the experiment batch; per-run math never crosses the axis so no
    collectives are introduced), everything else replicates. Falls back to
    fewer data axes / replication when the run count is indivisible."""
    dp = dp_axes(mesh)

    def f(path, leaf):
        if leaf.ndim == 0:
            return P()
        b = leaf.shape[0]
        for k in range(len(dp), 0, -1):
            n = _axsize(mesh, dp[:k])
            if b % n == 0 and b >= n:
                return P(dp[:k] if len(dp[:k]) > 1 else dp[0],
                         *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))
    return jax.tree_util.tree_map_with_path(f, stacked_shapes)


def shard_run_batch(tree: Any, mesh: Mesh) -> Any:
    """Place a stacked run-batch pytree on `mesh` per `run_batch_specs`."""
    specs = run_batch_specs(tree, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)

