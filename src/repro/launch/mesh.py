"""Meshes over the local devices.

Functions, not module-level constants, so importing this module never
touches jax device state (jax locks the device count on first use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the engine places arrays with
    NamedShardings and lets the compiler propagate them, so sharding must
    not enter the array types (Explicit axes, jax's default, make eager
    indexing of a sharded run axis an error)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices exist locally, as a (data, model) mesh (model=1)."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))


def make_batch_mesh(n_runs: int = 0):
    """Mesh for `repro.api.run_batch`: every local device on the data axis
    (the batch axis shards over it — sharding/specs.run_batch_specs). With
    `n_runs` > 0, clips to the largest device count that divides the run
    count so no run straddles devices."""
    n = len(jax.devices())
    if n_runs:
        while n > 1 and n_runs % n:
            n -= 1
    return _mesh((n, 1), ("data", "model"))


def make_cohort_mesh(flat: int = 0):
    """Mesh for fleet/flattened-client execution (`launch(FleetSpec,
    mesh=...)`): every local device on the data axis, clipped to the
    largest count dividing the flattened run×client axis — `shard_map`
    requires exact divisibility (sharding/specs.can_shard_flat falls
    back to the single-program vmap path otherwise, so the clip keeps
    every device useful instead of idling the whole mesh)."""
    n = len(jax.devices())
    if flat:
        while n > 1 and flat % n:
            n -= 1
    return _mesh((n, 1), ("data", "model"))
