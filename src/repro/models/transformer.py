"""Model factory: builds init / forward / loss / prefill / decode callables
for every assigned architecture family from an ArchConfig.

Structural choices (see DESIGN.md):
* Per-layer parameters are stacked on a leading L axis and consumed with
  ``jax.lax.scan`` — keeps HLO size O(1) in depth (the 80–94 layer
  configs compile in the time of one layer).
* The LM loss is computed in vocab-chunks (scan over the T axis) so the
  (B, T, V) logits tensor is never materialized — critical for the 256206-
  vocab seamless-m4t config.
* Decode uses ring-buffer KV caches when a sliding window is configured,
  making long-context decode bounded-memory for the dense sliding-window
  variant.
"""
from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ArchConfig
from repro.models.factored import (FACTORED_FORWARD_ATTR,
                                   make_decoder_factored)
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.layers import ACC

PyTree = Any
LOSS_CHUNK = 512

class Model(NamedTuple):
    cfg: ArchConfig
    init: Callable[[jax.Array], PyTree]
    forward: Callable[[PyTree, Dict[str, jax.Array]], jax.Array]
    loss_fn: Callable[[PyTree, Dict[str, jax.Array]], jax.Array]
    prefill: Optional[Callable]          # (params, batch) -> (logits, cache)
    decode: Optional[Callable]           # (params, token, cache, pos) -> (logits, cache)
    init_cache: Optional[Callable]       # (batch, seq_len, dtype) -> cache pytree
    # MoE decoders: (params, batch) -> rows routed to each held expert of
    # each MoE layer (MoE layers, experts held), forward only.
    route_counts: Optional[Callable] = None


def lm_eval_fn(model: "Model", test_batch: Dict[str, jax.Array]) -> Callable:
    """Held-out eval for an LM client: jitted mean negative NLL over a fixed
    {tokens, labels} batch (higher is better, matching the accuracy-style
    `Experiment.eval_fn` contract). This is the FL-engine hook that lets
    any `build_model` language model ride the same Experiment/serving
    paths as the paper CNN (DESIGN.md §13 transformer-client quickstart)."""
    batch = {k: jnp.asarray(v) for k, v in test_batch.items()}

    @jax.jit
    def nll(params):
        return -model.loss_fn(params, batch)
    return nll


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding (vocab-chunked loss)
# ---------------------------------------------------------------------------

def _embed_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    p = {"embed": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model)) * 0.02
                   ).astype(dtype),
         "final_norm": L.rms_norm_init(cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = L._he(k2, (cfg.d_model, cfg.vocab_size), dtype)
    return p


def _unembed_w(params, cfg):
    return (params["embed"].T if cfg.tie_embeddings else params["lm_head"])


def lm_logits(params, cfg, h):
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    return jnp.einsum("btd,dv->btv", h, _unembed_w(params, cfg),
                      preferred_element_type=ACC)


def chunked_xent(params, cfg, h, labels):
    """Mean next-token cross-entropy without materializing (B,T,V)."""
    b, t, d = h.shape
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    w = _unembed_w(params, cfg)
    chunk = min(LOSS_CHUNK, t)
    n = t // chunk
    hc = h[:, :n * chunk].reshape(b, n, chunk, d).swapaxes(0, 1)
    lc = labels[:, :n * chunk].reshape(b, n, chunk).swapaxes(0, 1)

    def step(tot, xs):
        hx, lx = xs
        logits = jnp.einsum("bcd,dv->bcv", hx, w, preferred_element_type=ACC)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(step, jnp.zeros((), ACC), (hc, lc))
    return tot / (b * n * chunk)


# ---------------------------------------------------------------------------
# Decoder block bodies (dense / moe / mla variants)
# ---------------------------------------------------------------------------

def _block_init(key, cfg, dtype, d_ff=None):
    """One decoder layer: attention, and a SwiGLU of width `d_ff` or, where
    `d_ff` is None, the MoE layer."""
    ks = jax.random.split(key, 4)
    p = {"ln1": L.rms_norm_init(cfg.d_model, dtype),
         "ln2": L.rms_norm_init(cfg.d_model, dtype)}
    p["attn"] = (L.mla_init(ks[0], cfg, dtype) if cfg.mla
                 else L.attn_init(ks[0], cfg, dtype))
    if d_ff is None:
        p["ffn"] = MOE.moe_init(ks[1], cfg, dtype)
    else:
        p["ffn"] = L.mlp_init(ks[1], cfg.d_model, d_ff, dtype)
    return p


def _block_ffn(p, cfg, x):
    """-> (x, balance loss, rows routed to each held expert or None)."""
    h = L.rms_norm(p["ln2"], x, cfg.norm_eps)
    if "router" in p["ffn"]:
        y, aux, sizes = MOE.moe_ffn(p["ffn"], cfg, h)
    else:
        y, aux, sizes = L.mlp(p["ffn"], h), 0.0, None
    return x + y, aux, sizes


def _block_fwd(p, cfg, x, positions):
    h = L.rms_norm(p["ln1"], x, cfg.norm_eps)
    if cfg.mla:
        c_kv, k_rope = L.mla_latent(p["attn"], cfg, h, positions)
        a = L.mla_attention(p["attn"], cfg, h, positions, c_kv, k_rope)
    else:
        a = L.self_attention(p["attn"], cfg, h, positions)
    x = x + a
    return _block_ffn(p, cfg, x)


def layer_groups(cfg: ArchConfig):
    """The decoder's stacked layer groups, in order: (params key, layers,
    SwiGLU width or None for the MoE layer). A MoE decoder's leading dense
    layers (`first_k_dense`, of width `dense_d_ff`) are a group of their
    own, scanned before the MoE layers."""
    k = cfg.first_k_dense if cfg.moe else 0
    groups = (("dense_layers", k, cfg.resolved_dense_d_ff),
              ("layers", cfg.n_layers - k, None if cfg.moe else cfg.d_ff))
    return [g for g in groups if g[1]]


# ---------------------------------------------------------------------------
# Dense / MoE / MLA decoder-only family (also chameleon VLM backbone)
# ---------------------------------------------------------------------------

def _stacked_init(key, cfg, n, init_one):
    return jax.vmap(lambda k: init_one(k, cfg, _dtype(cfg)))(
        jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Activation checkpointing of the decoder layer
# ---------------------------------------------------------------------------

# The decoder layer keeps its projection outputs for the backward when they
# take at most this share of the device's memory, and recomputes them
# otherwise.
KEEP_PROJ_SHARE = 0.25
KEEP_PROJ_POLICY = jax.checkpoint_policies.save_only_these_names(L.PROJ)
_log = logging.getLogger(__name__)


def kept_proj_bytes(cfg: ArchConfig, batch: int, seq: int, dtype) -> int:
    """Bytes that `KEEP_PROJ_POLICY` keeps for the backward of the
    decoder's layers over (batch, seq) tokens: the projection outputs
    (`layers.PROJ`) that the backward reads, or recomputes from. Those are
    every attention projection in the activations' dtype (q, k, v and o,
    whose residual sum the FFN's norm reads; MLA's query, latent, rope
    key, key and value up-projections and o) and the SwiGLU's gate and up
    as the f32 products it reads (the dense FFN's, or the MoE's shared
    experts'; routed experts are recomputed). The FFN's down projection
    feeds only the layer's output, which its backward does not read, so
    it is not kept. Each layer group (`layer_groups`) counts with its own
    FFN width."""
    d, h = cfg.d_model, cfg.n_heads
    if cfg.mla:
        m = cfg.mla
        attn = (h * (m.qk_nope_dim + m.qk_rope_dim) + m.kv_lora_rank
                + m.qk_rope_dim + h * m.qk_nope_dim + h * m.v_head_dim + d)
    else:
        hd = cfg.resolved_head_dim
        attn = (h + 2 * cfg.n_kv_heads) * hd + d
    total = 0
    for _, n, d_ff in layer_groups(cfg):
        if d_ff is None:
            ffn = 2 * cfg.moe.d_ff_expert * cfg.moe.n_shared_experts
        else:
            ffn = 2 * d_ff
        total += n * (attn * jnp.dtype(dtype).itemsize
                      + ffn * jnp.dtype(ACC).itemsize)
    return total * batch * seq


def device_bytes_limit() -> Optional[int]:
    """The smallest memory `bytes_limit` among the default backend's local
    devices, where the step's arrays live; None where a device reports no
    memory (the CPU)."""
    limits = [(d.memory_stats() or {}).get("bytes_limit")
              for d in jax.local_devices()]
    if not limits or None in limits:
        return None
    return min(limits)


def keeps_proj(nbytes: int, bytes_limit: Optional[int]) -> bool:
    return bytes_limit is not None and nbytes <= KEEP_PROJ_SHARE * bytes_limit


def decoder_remat(cfg: ArchConfig, batch: int, seq: int, dtype) -> Callable:
    """The checkpoint of one decoder layer for a step over (batch, seq)
    tokens: one that keeps the projection outputs where their bytes fit in
    `KEEP_PROJ_SHARE` of the device's memory, so the backward recomputes
    the norms, RoPE, SwiGLU and attention but none of the projection
    GEMMs; else the plain checkpoint, which recomputes the whole layer.
    The bytes are reckoned for the whole batch on one device, which
    over-counts a sharded batch; under `vmap` the step's batch axis is not
    seen. The kept path runs its layers under the `obs.REMAT_KEEP` scope,
    so the compiled step names it."""
    nbytes = kept_proj_bytes(cfg, batch, seq, dtype)
    limit = device_bytes_limit()
    keep = keeps_proj(nbytes, limit)
    _log.info("decoder %s: %s, projection outputs %d B, device limit %s B",
              cfg.name, "keeping" if keep else "recomputing", nbytes, limit)
    if not keep:
        return jax.checkpoint

    def remat(layer):
        # The layer runs inside a scan, which already keeps XLA from merging
        # the recompute into the forward. Without the checkpoint's own
        # barriers the backward reads the kept outputs where they lie; with
        # them it first copies each out of the scan's stack.
        kept = jax.checkpoint(layer, policy=KEEP_PROJ_POLICY,
                              prevent_cse=False)

        def scoped(carry, lp):
            with jax.named_scope(obs.REMAT_KEEP):
                return kept(carry, lp)
        return scoped
    return remat


def build_decoder_only(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)
    groups = layer_groups(cfg)

    def init(key):
        k1, k2 = jax.random.split(key)
        p = _embed_init(k1, cfg, dtype)
        for name, n, d_ff in groups:
            kg = k2 if name == "layers" else jax.random.fold_in(k2, 1)
            p[name] = _stacked_init(kg, cfg, n, functools.partial(
                _block_init, d_ff=d_ff))
        return p

    def backbone(params, tokens, collect=False):
        """-> (x, the layers' balance loss, and with `collect` the rows
        routed to each held expert of each MoE layer (n_moe, H))."""
        b, t = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def layer(carry, lp):
            x, aux = carry
            x, a, sizes = _block_fwd(lp, cfg, x, positions)
            return (x, aux + a), (sizes if collect else None)

        if cfg.remat:
            layer = decoder_remat(cfg, b, t, x.dtype)(layer)

        carry, counts = (x, jnp.zeros((), ACC)), []
        for name, n, _ in groups:
            carry, sizes = jax.lax.scan(layer, carry, params[name])
            if sizes is not None:
                counts.append(sizes)
        x, aux = carry
        if collect:
            return x, aux, jnp.concatenate(counts)
        return x, aux

    def forward(params, batch):
        x, _ = backbone(params, batch["tokens"])
        return lm_logits(params, cfg, x)

    # Factored-serving capability hook (models/factored.py): the dense GQA
    # family threads `LowRankDeltaPool` deltas through every matmul site
    # without densifying members. MoE/MLA variants have routing/latent
    # sites the factored path doesn't cover yet — they fall back to the
    # densified vmap in `PoolServer.from_pool`.
    if cfg.moe is None and cfg.mla is None:
        setattr(forward, FACTORED_FORWARD_ATTR, make_decoder_factored(cfg))

    def loss_fn(params, batch):
        x, aux = backbone(params, batch["tokens"])
        return chunked_xent(params, cfg, x, batch["labels"]) + aux

    def route_counts(params, batch):
        """Forward only: the rows routed to each held expert of each MoE
        layer, (MoE layers, experts held) int32, as the layers' grouped
        GEMMs take them."""
        return backbone(params, batch["tokens"], collect=True)[2]

    # ---- serving ---------------------------------------------------------
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    window = cfg.sliding_window

    def cache_len(seq_len):
        return min(seq_len, window) if window else seq_len

    def init_cache(batch, seq_len, dtype_c=None):
        dtype_c = dtype_c or dtype
        w = cache_len(seq_len)
        if cfg.mla:
            m = cfg.mla
            return {"c_kv": jnp.zeros((cfg.n_layers, batch, w, m.kv_lora_rank),
                                      dtype_c),
                    "k_rope": jnp.zeros((cfg.n_layers, batch, w, m.qk_rope_dim),
                                        dtype_c)}
        return {"k": jnp.zeros((cfg.n_layers, batch, w, kv, hd), dtype_c),
                "v": jnp.zeros((cfg.n_layers, batch, w, kv, hd), dtype_c)}

    def prefill(params, batch):
        """Process a full prompt; return last-token logits + filled cache."""
        tokens = batch["tokens"]
        b, t = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def layer(carry, lp):
            x, aux = carry
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            if cfg.mla:
                c_kv, k_rope = L.mla_latent(lp["attn"], cfg, h, positions)
                a = L.mla_attention(lp["attn"], cfg, h, positions, c_kv, k_rope)
                kv_out = (c_kv, k_rope)
            else:
                q, k, v = L.attn_qkv(lp["attn"], cfg, h, positions)
                a = L.attn_out(lp["attn"], L.flash_attention(
                    q, k, v, causal=True, window=window))
                kv_out = (k, v)
            x = x + a
            x, a2, _ = _block_ffn(lp, cfg, x)
            return (x, aux + a2), kv_out

        carry, per_group = (x, jnp.zeros((), ACC)), []
        for name, _, _ in groups:
            carry, kvs = jax.lax.scan(layer, carry, params[name])
            per_group.append(kvs)
        x = carry[0]
        kvs = _join(per_group)
        logits = lm_logits(params, cfg, x[:, -1:])
        if cfg.mla:
            cache = {"c_kv": kvs[0], "k_rope": kvs[1]}
        else:
            cache = {"k": kvs[0], "v": kvs[1]}
        # window-trim for ring-buffer layout
        if window and t > window:
            cache = jax.tree.map(lambda c: _ring_pack(c, t, window), cache)
        return logits, cache

    def _ring_pack(c, t, w):
        # entries i of ring hold absolute position p, p % w == i, latest.
        tail = c[:, :, t - w:]
        shift = (t - w) % w
        return jnp.roll(tail, shift, axis=2)

    def decode(params, token, cache, pos):
        """token: (B,1) int32; pos: () int32 absolute position."""
        b = token.shape[0]
        x = jnp.take(params["embed"], token, axis=0)
        positions = jnp.broadcast_to(pos[None], (b, 1))
        w = cache["k"].shape[2] if "k" in cache else cache["c_kv"].shape[2]
        slot = (pos % w) if window else pos
        idx = jnp.arange(w)
        if window:
            entry_pos = pos - ((pos - idx) % w)
        else:
            entry_pos = idx
        entry_pos = jnp.broadcast_to(entry_pos, (b, w))

        def layer(carry, xs):
            x, = carry
            if cfg.mla:
                lp, c_kv_l, k_rope_l = xs
                h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
                c_new, r_new = L.mla_latent(lp["attn"], cfg, h, positions)
                c_kv_l = jax.lax.dynamic_update_slice_in_dim(
                    c_kv_l, c_new.astype(c_kv_l.dtype), slot, axis=1)
                k_rope_l = jax.lax.dynamic_update_slice_in_dim(
                    k_rope_l, r_new.astype(k_rope_l.dtype), slot, axis=1)
                a = _mla_decode_attn(lp["attn"], cfg, h, positions,
                                     c_kv_l, k_rope_l, entry_pos, pos)
                x = x + a
                x, _, _ = _block_ffn(lp, cfg, x)
                return (x,), (c_kv_l, k_rope_l)
            lp, k_l, v_l = xs
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            q, k, v = L.attn_qkv(lp["attn"], cfg, h, positions)
            k_l = jax.lax.dynamic_update_slice_in_dim(
                k_l, k.astype(k_l.dtype), slot, axis=1)
            v_l = jax.lax.dynamic_update_slice_in_dim(
                v_l, v.astype(v_l.dtype), slot, axis=1)
            a = L.decode_attention(q, k_l, v_l, entry_pos,
                                   jnp.broadcast_to(pos, (b,)), window=window)
            x = x + L.attn_out(lp["attn"], a)
            x, _, _ = _block_ffn(lp, cfg, x)
            return (x,), (k_l, v_l)

        names = ("c_kv", "k_rope") if cfg.mla else ("k", "v")
        carry, per_group, start = (x,), [], 0
        for name, n, _ in groups:
            part = (lambda c: c) if len(groups) == 1 else (
                lambda c: c[start:start + n])
            carry, new = jax.lax.scan(layer, carry, (params[name],) + tuple(
                part(cache[c]) for c in names))
            per_group.append(new)
            start += n
        x, = carry
        new = _join(per_group)
        logits = lm_logits(params, cfg, x)
        if cfg.mla:
            cache = {"c_kv": new[0], "k_rope": new[1]}
        else:
            cache = {"k": new[0], "v": new[1]}
        return logits, cache

    return Model(cfg, init, forward, loss_fn, prefill, decode, init_cache,
                 route_counts if cfg.moe else None)


def _join(per_group):
    """Per-layer outputs of consecutive layer groups, joined on the layer
    axis."""
    if len(per_group) == 1:
        return per_group[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a), *per_group)


def _mla_decode_attn(p, cfg, h, positions, c_kv, k_rope, entry_pos, pos):
    """MLA attention over the latent cache with validity masking."""
    m = cfg.mla
    b = h.shape[0]
    s = c_kv.shape[1]
    valid = entry_pos[0] <= pos                       # (S,)
    # mask invalid latents by zeroing keys is wrong (softmax); instead add
    # mask inside: easiest is to call mla_attention then re-mask — here we
    # exploit causal+q_offset: set q_offset so that only entries <= pos pass.
    # Build explicit masked attention:
    q = L._proj(h, p["w_dq"]).reshape(b, 1, cfg.n_heads,
                                      m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = L.mla_rope(q_rope, positions, cfg)
    k_nope = L._proj(c_kv, p["w_uk"]).reshape(b, s, cfg.n_heads, m.qk_nope_dim)
    v = L._proj(c_kv, p["w_uv"]).reshape(b, s, cfg.n_heads, m.v_head_dim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], (b, s, cfg.n_heads, m.qk_rope_dim))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1).astype(ACC)
    sc = jnp.einsum("bthd,bshd->bths", qf * L.mla_scale(cfg), k.astype(ACC))
    sc = jnp.where(valid[None, None, None, :], sc, L.NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bths,bshd->bthd", pr, v.astype(ACC)).astype(h.dtype)
    return L._proj(o.reshape(b, 1, cfg.n_heads * m.v_head_dim), p["wo"])


# ---------------------------------------------------------------------------
# Hybrid (Zamba2): Mamba2 backbone + weight-tied shared attention block
# ---------------------------------------------------------------------------

def build_hybrid(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)
    every = cfg.shared_attn_every
    n_app = cfg.n_layers // every if every else 0

    def _mamba_layer_init(key, cfg_, dt):
        k1, k2 = jax.random.split(key)
        return {"ln": L.rms_norm_init(cfg_.d_model, dt),
                "mixer": SSM.mamba2_init(k1, cfg_, dt)}

    def init(key):
        ks = jax.random.split(key, 4)
        p = {**_embed_init(ks[0], cfg, dtype),
             "layers": _stacked_init(ks[1], cfg, cfg.n_layers,
                                     _mamba_layer_init)}
        if every:
            p["shared_attn"] = {
                "ln1": L.rms_norm_init(cfg.d_model, dtype),
                "attn": L.attn_init(ks[2], cfg, dtype),
                "ln2": L.rms_norm_init(cfg.d_model, dtype),
                "mlp": L.mlp_init(ks[3], cfg.d_model, cfg.d_ff, dtype)}
        return p

    def _shared_block(sp, x, positions):
        h = L.rms_norm(sp["ln1"], x, cfg.norm_eps)
        x = x + L.self_attention(sp["attn"], cfg, h, positions)
        h = L.rms_norm(sp["ln2"], x, cfg.norm_eps)
        return x + L.mlp(sp["mlp"], h)

    def backbone(params, tokens):
        """Segmented: scan over each run of `every` Mamba2 layers, apply the
        weight-tied shared block between segments (no cond-in-scan — both
        cleaner HLO and exact cost attribution)."""
        b, t = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        sp = params.get("shared_attn")

        def layer(carry, lp):
            x, = carry
            x = x + SSM.mamba2_block(lp["mixer"], cfg,
                                     L.rms_norm(lp["ln"], x, cfg.norm_eps))
            return (x,), None

        if cfg.remat:
            layer = jax.checkpoint(layer)
        n_seg = n_app if every else 1
        seg_len = cfg.n_layers // n_seg
        for si in range(n_seg):
            seg_params = jax.tree.map(
                lambda a: a[si * seg_len:(si + 1) * seg_len],
                params["layers"])
            (x,), _ = jax.lax.scan(layer, (x,), seg_params)
            if every:
                x = _shared_block(sp, x, positions)
        return x

    def forward(params, batch):
        return lm_logits(params, cfg, backbone(params, batch["tokens"]))

    def loss_fn(params, batch):
        x = backbone(params, batch["tokens"])
        return chunked_xent(params, cfg, x, batch["labels"])

    dm = SSM.mamba2_dims(cfg)
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    conv_dim = dm.d_inner + 2 * dm.state

    def init_cache(batch, seq_len, dtype_c=None):
        dtype_c = dtype_c or dtype
        c = {"ssm": jnp.zeros((cfg.n_layers, batch, dm.n_heads, dm.state,
                               dm.head_dim), ACC),
             "conv": jnp.zeros((cfg.n_layers, batch, dm.conv_width - 1,
                                conv_dim), dtype_c)}
        if every:
            c["shared_k"] = jnp.zeros((n_app, batch, seq_len, kv, hd), dtype_c)
            c["shared_v"] = jnp.zeros((n_app, batch, seq_len, kv, hd), dtype_c)
        return c

    def prefill(params, batch):
        tokens = batch["tokens"]
        b, t = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        sp = params.get("shared_attn")
        cache = init_cache(b, t)

        # unrolled over the (few) shared applications, scanned inside
        def seg_layer(carry, lp):
            x, = carry
            qd, kd, vd, ld, xh, z, ncv = SSM._mamba2_qkvd(
                lp["mixer"], cfg,
                L.rms_norm(lp["ln"], x, cfg.norm_eps))
            y, st = SSM.gla_chunked(qd, kd, vd, ld,
                                    chunk=min(cfg.ssm.chunk_size, t))
            y = y + xh * lp["mixer"]["D"][None, None, :, None].astype(x.dtype)
            y = y.reshape(b, t, dm.d_inner)
            y = L.rms_norm(lp["mixer"]["norm"], y * jax.nn.silu(z),
                           cfg.norm_eps)
            x = x + jnp.einsum("btf,fd->btd", y, lp["mixer"]["w_out"],
                               preferred_element_type=ACC).astype(x.dtype)
            return (x,), (st, ncv)

        n_seg = n_app if every else 1
        seg_len = cfg.n_layers // n_seg
        ssm_states, conv_states, sk, sv = [], [], [], []
        for si in range(n_seg):
            seg_params = jax.tree.map(
                lambda a: a[si * seg_len:(si + 1) * seg_len], params["layers"])
            (x,), (sts, ncvs) = jax.lax.scan(seg_layer, (x,), seg_params)
            ssm_states.append(sts)
            conv_states.append(ncvs)
            if every:
                h = L.rms_norm(sp["ln1"], x, cfg.norm_eps)
                q, k, v = L.attn_qkv(sp["attn"], cfg, h, positions)
                a = L.attn_out(sp["attn"],
                               L.flash_attention(q, k, v, causal=True))
                x = x + a
                h2 = L.rms_norm(sp["ln2"], x, cfg.norm_eps)
                x = x + L.mlp(sp["mlp"], h2)
                sk.append(k)
                sv.append(v)
        cache["ssm"] = jnp.concatenate(ssm_states, 0)
        cache["conv"] = jnp.concatenate(conv_states, 0)
        if every:
            cache["shared_k"] = jnp.stack(sk)
            cache["shared_v"] = jnp.stack(sv)
        logits = lm_logits(params, cfg, x[:, -1:])
        return logits, cache

    def decode(params, token, cache, pos):
        b = token.shape[0]
        x = jnp.take(params["embed"], token, axis=0)
        positions = jnp.broadcast_to(pos[None], (b, 1))
        sp = params.get("shared_attn")
        s_len = cache["shared_k"].shape[2] if every else 0

        def _apply_shared(x, app_idx, sk, sv):
            h = L.rms_norm(sp["ln1"], x, cfg.norm_eps)
            q, k, v = L.attn_qkv(sp["attn"], cfg, h, positions)
            k_l = jax.lax.dynamic_slice_in_dim(sk, app_idx, 1, 0)[0]
            v_l = jax.lax.dynamic_slice_in_dim(sv, app_idx, 1, 0)[0]
            k_l = jax.lax.dynamic_update_slice_in_dim(
                k_l, k.astype(k_l.dtype), pos, axis=1)
            v_l = jax.lax.dynamic_update_slice_in_dim(
                v_l, v.astype(v_l.dtype), pos, axis=1)
            entry_pos = jnp.broadcast_to(jnp.arange(s_len), (b, s_len))
            a = L.decode_attention(q, k_l, v_l, entry_pos,
                                   jnp.broadcast_to(pos, (b,)))
            x = x + L.attn_out(sp["attn"], a)
            h2 = L.rms_norm(sp["ln2"], x, cfg.norm_eps)
            x = x + L.mlp(sp["mlp"], h2)
            sk = jax.lax.dynamic_update_slice_in_dim(sk, k_l[None], app_idx, 0)
            sv = jax.lax.dynamic_update_slice_in_dim(sv, v_l[None], app_idx, 0)
            return x, sk, sv

        def layer(carry, xs):
            x, = carry
            lp, st, cv = xs
            h = L.rms_norm(lp["ln"], x, cfg.norm_eps)
            y, st, cv = SSM.mamba2_decode(lp["mixer"], cfg, h, st, cv)
            return (x + y,), (st, cv)

        # segmented like backbone(): scan each Mamba2 run, shared block
        # (with its per-application KV cache) between segments
        n_seg = n_app if every else 1
        seg_len = cfg.n_layers // n_seg
        sk = cache.get("shared_k")
        sv = cache.get("shared_v")
        sts_all, cvs_all = [], []
        for si in range(n_seg):
            sl = slice(si * seg_len, (si + 1) * seg_len)
            seg = jax.tree.map(lambda a: a[sl], params["layers"])
            (x,), (sts, cvs) = jax.lax.scan(
                layer, (x,), (seg, cache["ssm"][sl], cache["conv"][sl]))
            sts_all.append(sts)
            cvs_all.append(cvs)
            if every:
                x, sk, sv = _apply_shared(x, si, sk, sv)
        new_cache = {"ssm": jnp.concatenate(sts_all, 0),
                     "conv": jnp.concatenate(cvs_all, 0)}
        if every:
            new_cache["shared_k"], new_cache["shared_v"] = sk, sv
        logits = lm_logits(params, cfg, x)
        return logits, new_cache

    return Model(cfg, init, forward, loss_fn, prefill, decode, init_cache)


# ---------------------------------------------------------------------------
# RWKV6 (pure SSM family)
# ---------------------------------------------------------------------------

def build_rwkv(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)

    def _layer_init(key, cfg_, dt):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"ln1": L.rms_norm_init(cfg_.d_model, dt),
                "mixer": SSM.rwkv6_init(k1, cfg_, dt),
                "ln2": L.rms_norm_init(cfg_.d_model, dt),
                "ffn": L.mlp_init(k2, cfg_.d_model, cfg_.d_ff, dt)}

    def init(key):
        k1, k2 = jax.random.split(key)
        return {**_embed_init(k1, cfg, dtype),
                "layers": _stacked_init(k2, cfg, cfg.n_layers, _layer_init)}

    def backbone(params, tokens):
        b, t = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)

        def layer(carry, lp):
            x, = carry
            x = x + SSM.rwkv6_block(lp["mixer"], cfg,
                                    L.rms_norm(lp["ln1"], x, cfg.norm_eps))
            x = x + L.mlp(lp["ffn"], L.rms_norm(lp["ln2"], x, cfg.norm_eps))
            return (x,), None

        if cfg.remat:
            layer = jax.checkpoint(layer)
        (x,), _ = jax.lax.scan(layer, (x,), params["layers"])
        return x

    def forward(params, batch):
        return lm_logits(params, cfg, backbone(params, batch["tokens"]))

    def loss_fn(params, batch):
        return chunked_xent(params, cfg, backbone(params, batch["tokens"]),
                            batch["labels"])

    s = cfg.ssm
    n_heads = cfg.d_model // s.head_dim

    def init_cache(batch, seq_len, dtype_c=None):
        dtype_c = dtype_c or dtype
        return {"state": jnp.zeros((cfg.n_layers, batch, n_heads, s.head_dim,
                                    s.head_dim), ACC),
                "x_prev": jnp.zeros((cfg.n_layers, batch, 1, cfg.d_model),
                                    dtype_c)}

    def prefill(params, batch):
        tokens = batch["tokens"]
        b, t = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)

        def layer(carry, lp):
            x, = carry
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            r, k, v, g, ld, x_last = SSM._rwkv6_inputs(
                lp["mixer"], cfg, h, jnp.zeros_like(h[:, :1]))
            y, st = SSM.gla_chunked(r, k, v, ld, chunk=min(32, t),
                                    bonus=jnp.exp(lp["mixer"]["bonus_u"]))
            y = L.rms_norm(lp["mixer"]["ln_x"], y.reshape(b, t, cfg.d_model),
                           cfg.norm_eps) * g
            x = x + jnp.einsum("btd,df->btf", y, lp["mixer"]["w_o"],
                               preferred_element_type=ACC).astype(x.dtype)
            x = x + L.mlp(lp["ffn"], L.rms_norm(lp["ln2"], x, cfg.norm_eps))
            return (x,), (st, x_last)

        (x,), (sts, xls) = jax.lax.scan(layer, (x,), params["layers"])
        return lm_logits(params, cfg, x[:, -1:]), \
            {"state": sts, "x_prev": xls}

    def decode(params, token, cache, pos):
        b = token.shape[0]
        x = jnp.take(params["embed"], token, axis=0)

        def layer(carry, xs):
            x, = carry
            lp, st, xp = xs
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            y, st, xp = SSM.rwkv6_decode(lp["mixer"], cfg, h, st, xp)
            x = x + y
            x = x + L.mlp(lp["ffn"], L.rms_norm(lp["ln2"], x, cfg.norm_eps))
            return (x,), (st, xp)

        (x,), (sts, xps) = jax.lax.scan(
            layer, (x,), (params["layers"], cache["state"], cache["x_prev"]))
        return lm_logits(params, cfg, x), {"state": sts, "x_prev": xps}

    return Model(cfg, init, forward, loss_fn, prefill, decode, init_cache)


# ---------------------------------------------------------------------------
# Encoder-decoder (seamless-m4t): stubbed audio frontend feeds embeddings
# ---------------------------------------------------------------------------

def build_encdec(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)

    def _enc_init(key, cfg_, dt):
        k1, k2 = jax.random.split(key)
        return {"ln1": L.rms_norm_init(cfg_.d_model, dt),
                "attn": L.attn_init(k1, cfg_, dt),
                "ln2": L.rms_norm_init(cfg_.d_model, dt),
                "ffn": L.mlp_init(k2, cfg_.d_model, cfg_.d_ff, dt)}

    def _dec_init(key, cfg_, dt):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"ln1": L.rms_norm_init(cfg_.d_model, dt),
                "self_attn": L.attn_init(k1, cfg_, dt),
                "ln_x": L.rms_norm_init(cfg_.d_model, dt),
                "cross_attn": L.cross_attn_init(k2, cfg_, dt),
                "ln2": L.rms_norm_init(cfg_.d_model, dt),
                "ffn": L.mlp_init(k3, cfg_.d_model, cfg_.d_ff, dt)}

    def init(key):
        ks = jax.random.split(key, 3)
        return {**_embed_init(ks[0], cfg, dtype),
                "encoder": _stacked_init(ks[1], cfg, cfg.n_encoder_layers,
                                         _enc_init),
                "decoder": _stacked_init(ks[2], cfg, cfg.n_layers, _dec_init)}

    def encode(params, src):
        """src: (B, T_src, D) precomputed frame embeddings (frontend stub)."""
        b, t, _ = src.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def layer(carry, lp):
            x, = carry
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            q, k, v = L.attn_qkv(lp["attn"], cfg, h, positions)
            x = x + L.attn_out(lp["attn"],
                               L.flash_attention(q, k, v, causal=False))
            x = x + L.mlp(lp["ffn"], L.rms_norm(lp["ln2"], x, cfg.norm_eps))
            return (x,), None

        if cfg.remat:
            layer = jax.checkpoint(layer)
        (x,), _ = jax.lax.scan(layer, (src.astype(dtype),), params["encoder"])
        return x

    def _cross_kv(lp, enc_out):
        b, t, _ = enc_out.shape
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        k = L._proj(enc_out, lp["cross_attn"]["wk"]).reshape(b, t, kv, hd)
        v = L._proj(enc_out, lp["cross_attn"]["wv"]).reshape(b, t, kv, hd)
        return k, v

    def _decoder_fwd(params, tokens, enc_out):
        b, t = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def layer(carry, lp):
            x, = carry
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            x = x + L.self_attention(lp["self_attn"], cfg, h, positions)
            h = L.rms_norm(lp["ln_x"], x, cfg.norm_eps)
            x = x + L.cross_attention(lp["cross_attn"], cfg, h,
                                      _cross_kv(lp, enc_out))
            x = x + L.mlp(lp["ffn"], L.rms_norm(lp["ln2"], x, cfg.norm_eps))
            return (x,), None

        if cfg.remat:
            layer = jax.checkpoint(layer)
        (x,), _ = jax.lax.scan(layer, (x,), params["decoder"])
        return x

    def forward(params, batch):
        enc_out = encode(params, batch["src_embeds"])
        return lm_logits(params, cfg, _decoder_fwd(params, batch["tokens"],
                                                   enc_out))

    def loss_fn(params, batch):
        enc_out = encode(params, batch["src_embeds"])
        x = _decoder_fwd(params, batch["tokens"], enc_out)
        return chunked_xent(params, cfg, x, batch["labels"])

    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    def init_cache(batch, seq_len, dtype_c=None, src_len=None):
        dtype_c = dtype_c or dtype
        src_len = src_len or seq_len
        return {"k": jnp.zeros((cfg.n_layers, batch, seq_len, kv, hd), dtype_c),
                "v": jnp.zeros((cfg.n_layers, batch, seq_len, kv, hd), dtype_c),
                "cross_k": jnp.zeros((cfg.n_layers, batch, src_len, kv, hd),
                                     dtype_c),
                "cross_v": jnp.zeros((cfg.n_layers, batch, src_len, kv, hd),
                                     dtype_c)}

    def prefill(params, batch):
        """Encode source and run decoder over the target prefix."""
        enc_out = encode(params, batch["src_embeds"])
        tokens = batch["tokens"]
        b, t = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        def layer(carry, lp):
            x, = carry
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            q, k, v = L.attn_qkv(lp["self_attn"], cfg, h, positions)
            x = x + L.attn_out(lp["self_attn"],
                               L.flash_attention(q, k, v, causal=True))
            h = L.rms_norm(lp["ln_x"], x, cfg.norm_eps)
            ck, cv = _cross_kv(lp, enc_out)
            x = x + L.cross_attention(lp["cross_attn"], cfg, h, (ck, cv))
            x = x + L.mlp(lp["ffn"], L.rms_norm(lp["ln2"], x, cfg.norm_eps))
            return (x,), (k, v, ck, cv)

        (x,), (ks, vs, cks, cvs) = jax.lax.scan(layer, (x,), params["decoder"])
        return lm_logits(params, cfg, x[:, -1:]), \
            {"k": ks, "v": vs, "cross_k": cks, "cross_v": cvs}

    def decode(params, token, cache, pos):
        b = token.shape[0]
        x = jnp.take(params["embed"], token, axis=0)
        positions = jnp.broadcast_to(pos[None], (b, 1))
        s = cache["k"].shape[2]
        s_src = cache["cross_k"].shape[2]
        entry_pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        src_pos = jnp.broadcast_to(jnp.arange(s_src), (b, s_src))
        big = jnp.broadcast_to(jnp.asarray(s_src + 1), (b,))

        def layer(carry, xs):
            x, = carry
            lp, k_l, v_l, ck, cv = xs
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            q, k, v = L.attn_qkv(lp["self_attn"], cfg, h, positions)
            k_l = jax.lax.dynamic_update_slice_in_dim(
                k_l, k.astype(k_l.dtype), pos, axis=1)
            v_l = jax.lax.dynamic_update_slice_in_dim(
                v_l, v.astype(v_l.dtype), pos, axis=1)
            a = L.decode_attention(q, k_l, v_l, entry_pos,
                                   jnp.broadcast_to(pos, (b,)))
            x = x + L.attn_out(lp["self_attn"], a)
            h = L.rms_norm(lp["ln_x"], x, cfg.norm_eps)
            qc = L._proj(h, lp["cross_attn"]["wq"]).reshape(
                b, 1, cfg.n_heads, hd)
            ac = L.decode_attention(qc, ck, cv, src_pos, big)
            x = x + L.attn_out(lp["cross_attn"], ac)
            x = x + L.mlp(lp["ffn"], L.rms_norm(lp["ln2"], x, cfg.norm_eps))
            return (x,), (k_l, v_l)

        (x,), (ks, vs) = jax.lax.scan(
            layer, (x,), (params["decoder"], cache["k"], cache["v"],
                          cache["cross_k"], cache["cross_v"]))
        logits = lm_logits(params, cfg, x)
        return logits, {**cache, "k": ks, "v": vs}

    return Model(cfg, init, forward, loss_fn, prefill, decode, init_cache)


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

def build_model(cfg: ArchConfig) -> Model:
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        return build_decoder_only(cfg)
    if cfg.family == "hybrid":
        return build_hybrid(cfg)
    if cfg.family == "ssm":
        if cfg.ssm.kind == "rwkv6":
            return build_rwkv(cfg)
        return build_hybrid(cfg)
    if cfg.family == "encdec":
        return build_encdec(cfg)
    if cfg.family == "cnn":
        from repro.models.cnn import build_cnn
        return build_cnn(cfg)
    raise ValueError(cfg.family)
