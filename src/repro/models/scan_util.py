"""Scan helpers shared by model internals.

XLA's cost analysis counts a while body once regardless of trip count
(see launch/dryrun.py). The dry-run therefore sets REPRO_INNER_UNROLL=full
so *inner* scans (flash-attention KV blocks, GLA chunk scans, the chunked
LM loss) are fully unrolled in the lowered module — their cost then lands
inside the (layer-)scan body that the two-pass correction scales exactly.
Normal execution keeps rolled loops.

REPRO_ATTN_BLOCK / REPRO_GLA_CHUNK let the dry-run coarsen the inner tile
sizes to bound the unrolled HLO size (FLOPs are tile-size-invariant).

REPRO_ATTN_BLOCK and REPRO_GQA_REPEAT act on the jnp attention scan alone
(`layers._scan_attention`): a call that takes the TPU kernel
(`kernels/ops.attention_fits`) picks its own blocks and reads grouped KV
heads without a copy. REPRO_ATTN_SEQ_SHARD keeps every call on the scan.
"""
from __future__ import annotations

import os

import jax


def inner_scan(f, init, xs, length=None):
    kw = {}
    if os.environ.get("REPRO_INNER_UNROLL") == "full":
        kw["unroll"] = True
    return jax.lax.scan(f, init, xs, length=length, **kw)


def attn_block_override(default: int) -> int:
    return int(os.environ.get("REPRO_ATTN_BLOCK", default))


def gla_chunk_override(default: int) -> int:
    return int(os.environ.get("REPRO_GLA_CHUNK", default))


# ---------------------------------------------------------------------------
# §Perf hillclimb levers (env-gated so baseline and optimized variants lower
# from the same source; see EXPERIMENTS.md §Perf)
# ---------------------------------------------------------------------------

def attn_seq_shard_axes():
    """REPRO_ATTN_SEQ_SHARD: '' (off) | 'single' | 'multi'.

    Sequence-parallel attention: shard the query time axis over `model`
    instead of heads. Fixes the head-indivisibility pathology (e.g. qwen2-7b:
    28 heads % 16-way TP != 0 forces GSPMD into replicate+all-reduce); KV is
    small under GQA, so the per-layer KV all-gather is cheap.
    Returns (batch_axes, seq_axis) or None."""
    v = os.environ.get("REPRO_ATTN_SEQ_SHARD", "")
    if not v:
        return None
    batch = ("pod", "data") if v == "multi" else ("data",)
    return batch, "model"


def gqa_repeat_mode() -> bool:
    """REPRO_GQA_REPEAT=1: expand KV to full head count before attention so
    every attention tensor shards cleanly over the model axis (the grouped
    5D form leaves a KV=4..8 axis no 16-way mesh can shard)."""
    return os.environ.get("REPRO_GQA_REPEAT", "") == "1"


def act_shard_axes():
    """REPRO_ACT_SHARD: '' | 'single' | 'multi' — pin layer activations to
    batch-sharded layout (MaxText-style constraints). Without it GSPMD may
    reshard (B,T,F) activations to batch-replicated/feature-sharded inside
    FFN layers, moving multi-GB tensors across the mesh every layer."""
    v = os.environ.get("REPRO_ACT_SHARD", "")
    if not v:
        return None
    return ("pod", "data") if v == "multi" else ("data",)


def constrain_act(x, *, hidden=False):
    """x: (B, T, D) residual or (B, T, F) FFN hidden."""
    axes = act_shard_axes()
    if axes is None:
        return x
    from jax.sharding import PartitionSpec as P
    ba = axes if len(axes) > 1 else axes[0]
    spec = P(ba, None, "model") if hidden else P(ba, None, None)
    return jax.lax.with_sharding_constraint(x, spec)
