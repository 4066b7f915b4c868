"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (jax locks the device count on first use — the
dry-run must set XLA_FLAGS before any jax call).

Target hardware: TPU v5e pod slices.
  single-pod : (data=16, model=16)            = 256 chips
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips
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


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


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


# TPU v5e roofline constants (per chip) — used by repro.analysis.roofline
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
