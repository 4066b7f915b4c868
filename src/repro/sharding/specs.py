"""Partition rules: pytree-of-shapes → pytree-of-PartitionSpec.

Strategy (GSPMD, MaxText-style logical rules):
* Tensor parallelism over the ``model`` axis: attention heads / FFN hidden /
  MoE expert axis.
* FSDP (ZeRO-3-style) parameter sharding over the data axes: the non-TP
  matrix dimension of every large weight is sharded over ("pod","data") when
  divisible — all-gathered per layer by GSPMD during the forward pass.
* Stacked-layer leading axes (paths under layers/encoder/decoder) are never
  sharded (they are scanned).
* Anything small or indivisible replicates.
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


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


# (substring, shard_dim_for_model, shard_dim_for_fsdp) relative to the
# *trailing* dims (negative indices), applied when divisible.
_RULES = [
    ("embed", -2, -1),            # (V, D): V over model, D over fsdp
    ("lm_head", -1, -2),          # (D, V): V over model
    ("router", None, -2),
    ("w_gate", -1, -2), ("w_up", -1, -2), ("w_down", -2, -1),
    ("wq", -1, -2), ("wk", -1, -2), ("wv", -1, -2), ("wo", -2, -1),
    ("bq", -1, None), ("bk", -1, None), ("bv", -1, None),
    ("w_dq", -1, -2), ("w_dkv", None, -2), ("w_kr", None, -2),
    ("w_uk", -1, None), ("w_uv", -1, None),
    ("w_in", -1, -2), ("w_out", -2, -1), ("conv_w", -1, None),
    ("w_r", -1, -2), ("w_k", -1, -2), ("w_v", -1, -2), ("w_g", -1, -2),
    ("w_o", -2, -1), ("w_lora_a", None, -2), ("w_lora_b", -1, None),
    ("fc1", -1, -2), ("fc2", -2, -1), ("c1", None, None),
]

# MoE expert stacks: (L, E, d, f) under a decoder's stacked "layers" —
# expert-parallel over model axis. A stacked dense FFN (L, d, f), as in a
# MoE decoder's leading "dense_layers", is not one, and takes _RULES.
_EXPERT_KEYS = ("ffn/w_gate", "ffn/w_up", "ffn/w_down")


def _leaf_spec(path: str, shape, mesh: Mesh, fsdp: bool) -> P:
    nd = len(shape)
    if nd <= 1 or max(shape) < 1024:
        return P()
    model_n = mesh.shape["model"]
    fsdp_ax = dp_axes(mesh)
    fsdp_n = _axsize(mesh, fsdp_ax)
    spec = [None] * nd

    # expert-parallel: shard the expert axis (dim -3 of (L, E, d, f) stacks)
    if any(k in path for k in _EXPERT_KEYS) and "shared" not in path and nd >= 4:
        e_dim = nd - 3
        if shape[e_dim] % model_n == 0:
            spec[e_dim] = "model"
            if fsdp and shape[-2] % fsdp_n == 0:
                spec[-2] = fsdp_ax
            return P(*spec)

    for key, mdim, fdim in _RULES:
        if key in path.split("/")[-1] or f"/{key}" in path:
            if mdim is not None and shape[mdim] % model_n == 0:
                spec[mdim] = "model"
            if fsdp and fdim is not None and shape[fdim] % fsdp_n == 0 \
                    and spec[fdim % nd] is None:
                spec[fdim] = fsdp_ax
            return P(*spec)

    # generic fallback: last dim over model, biggest other dim over fsdp
    if shape[-1] % model_n == 0 and shape[-1] >= model_n * 64:
        spec[-1] = "model"
    if fsdp and nd >= 2 and shape[-2] % fsdp_n == 0 and shape[-2] >= fsdp_n:
        spec[-2] = fsdp_ax
    return P(*spec)


def param_specs(shapes: Any, mesh: Mesh, fsdp: bool = True) -> Any:
    """shapes: pytree of ShapeDtypeStruct (or arrays)."""
    def f(path, leaf):
        return _leaf_spec(_path_str(path), leaf.shape, mesh, fsdp)
    return jax.tree_util.tree_map_with_path(f, shapes)


def batch_specs(batch_shapes: Any, mesh: Mesh) -> Any:
    """Batch dim over all data axes (falls back to partial/none if
    indivisible)."""
    dp = dp_axes(mesh)

    def f(path, leaf):
        if leaf.ndim == 0:
            return P()
        b = leaf.shape[0]
        for k in range(len(dp), 0, -1):
            if b % _axsize(mesh, dp[:k]) == 0 and b >= _axsize(mesh, dp[:k]):
                return P(dp[:k] if len(dp[:k]) > 1 else dp[0],
                         *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))
    return jax.tree_util.tree_map_with_path(f, batch_shapes)


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


def cache_specs(cache_shapes: Any, mesh: Mesh) -> Any:
    """Decode caches: (L, B, S, ...) — B over data axes when divisible,
    sequence/window axis over `model` (flash-decoding layout), H of SSM
    states over `model`."""
    dp = dp_axes(mesh)
    model_n = mesh.shape["model"]

    def f(path, leaf):
        p = _path_str(path)
        nd = leaf.ndim
        spec = [None] * nd
        if nd < 3:
            return P(*spec)
        b_dim = 1                                  # (L, B, ...)
        s_dim = 2
        b = leaf.shape[b_dim]
        rem_dp = dp
        for k in range(len(dp), 0, -1):
            if b % _axsize(mesh, dp[:k]) == 0 and b >= _axsize(mesh, dp[:k]):
                spec[b_dim] = dp[:k] if len(dp[:k]) > 1 else dp[0]
                rem_dp = dp[k:]
                break
        else:
            rem_dp = dp
        if "ssm" in p or "state" in p:
            # (L, B, H, K, V): shard heads over model
            if leaf.shape[2] % model_n == 0:
                spec[2] = "model"
            return P(*spec)
        if "conv" in p or "x_prev" in p:
            if leaf.shape[-1] % model_n == 0:
                spec[-1] = "model"
            return P(*spec)
        # attention KV / latent caches: seq axis over model (+ leftover dp)
        seq_axes = ("model",) + tuple(rem_dp) if spec[b_dim] is None else ("model",)
        n = _axsize(mesh, seq_axes)
        if leaf.shape[s_dim] % n == 0 and leaf.shape[s_dim] >= n:
            spec[s_dim] = seq_axes if len(seq_axes) > 1 else seq_axes[0]
        elif leaf.shape[s_dim] % model_n == 0:
            spec[s_dim] = "model"
        return P(*spec)
    return jax.tree_util.tree_map_with_path(f, cache_shapes)
