"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships three pieces (see EXAMPLE.md):
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrappers (interpret=True on CPU hosts)
  ref.py    — pure-jnp oracles the tests assert_allclose against
"""
from repro.kernels import ops, ref
from repro.kernels.chunk_scan import gla_chunk_pallas
from repro.kernels.pool_distance import factor_gram, pool_distance_stats

__all__ = ["ops", "ref", "pool_distance_stats", "factor_gram",
           "gla_chunk_pallas"]
