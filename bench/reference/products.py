"""The products of the plain references, in the precision a check asks for.

Every product is computed from f32 operands at `HIGHEST`, so that the
reference is exact to f32 on any backend. `mode` lowers it on purpose,
for the controls that a limit is set against:

- None: the f32 operands as they are;
- "bfloat16_3x": the three-pass product hi*hi + hi*lo + lo*hi of each
  operand's bfloat16 head and bfloat16 remainder (what precision `high`
  does on a TPU), written out so that the CPU computes it too;
- any other dtype name: both operands rounded to that dtype first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
BF16 = jnp.bfloat16


def product(fn, a, b, mode=None):
    """fn(a, b, precision) for a function bilinear in a and b."""
    a, b = a.astype(F32), b.astype(F32)
    if mode is None:
        return fn(a, b, HI)
    if mode == "bfloat16_3x":
        a_hi, b_hi = a.astype(BF16).astype(F32), b.astype(BF16).astype(F32)
        a_lo = (a - a_hi).astype(BF16).astype(F32)
        b_lo = (b - b_hi).astype(BF16).astype(F32)
        return fn(a_hi, b_hi, HI) + (fn(a_hi, b_lo, HI) + fn(a_lo, b_hi, HI))
    dt = jnp.dtype(mode)
    return fn(a.astype(dt).astype(F32), b.astype(dt).astype(F32), HI)
