"""Shared transformer building blocks (pure JAX, pytree params).

Conventions
-----------
* Activations: (B, T, D). Attention heads live in the last-but-one axis of
  intermediate tensors: q (B, T, H, hd).
* Params are plain nested dicts of jnp arrays; layer-stacked modules carry a
  leading L axis and are consumed by ``jax.lax.scan``.
* All matmuls accumulate in f32 (``preferred_element_type``) so bf16 params
  are MXU-friendly without precision collapse.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro import obs
from repro.kernels import ops

ACC = jnp.float32

# The name of a weight projection's output (`_proj`, and the SwiGLU's gate
# and up), for a checkpoint policy that keeps these outputs for the
# backward instead of recomputing their GEMMs (`transformer.py`,
# `decoder_remat`). Outside such a policy the name is an identity that
# lowers to nothing.
PROJ = "proj"


def _he(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p, x, eps=1e-5):
    xf = x.astype(ACC)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(ACC)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=ACC) / head_dim))


def yarn_mscale(factor, mscale):
    """YaRN's attention factor: 0.1 * mscale * ln(factor) + 1 (1 where the
    context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim, theta, yarn):
    """YaRN's inverse frequencies for a rope of `dim` dims (DeepSeek-V2's
    `DeepseekV2YarnRotaryEmbedding`): pair i keeps theta^(-2i/dim) below
    the correction range [low, high] and takes it divided by `factor` above
    it, blended linearly across; low and high are the dims at which a
    frequency turns `beta_fast` and `beta_slow` times over the original
    context, floor and ceil of dim * ln(L / (2 pi beta)) / (2 ln theta)."""
    def turns(beta):
        return (dim * math.log(yarn.original_max_position
                               / (beta * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(turns(yarn.beta_fast)), 0)
    high = min(math.ceil(turns(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=ACC) - low) / (high - low),
                    0.0, 1.0)
    extra = rope_freqs(dim, theta)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x, positions, theta, freqs=None, amp=1.0):
    """x: (..., T, H, hd) rotated pairwise; positions: (..., T). `freqs`
    (hd/2,) replaces the plain inverse frequencies and `amp` scales cos and
    sin (YaRN)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    angles = positions.astype(ACC)[..., None] * freqs   # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., T, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    x1, x2 = jnp.split(x.astype(ACC), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window) — chunked "flash" formulation.
#
# On TPU, self-attention of a whole bf16 sequence runs jax's splash
# attention kernels, forward and backward, through `kernels/ops.attention`.
# The jnp scan below is the path for every other call (cached decode,
# cross-attention, short windows, f32 models), the CPU path, and the
# kernel's oracle.
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gqa_expand(q, n_kv):
    """(B,T,H,hd) -> (B,T,KV,G,hd) groups."""
    b, t, h, hd = q.shape
    return q.reshape(b, t, n_kv, h // n_kv, hd)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    kv_block=512, scale=None):
    """Attention of q over (k, v): the splash kernel
    (`kernels/ops.attention`) where the call's own shapes, dtypes,
    precision and platform allow it (`ops.attention_fits`), else the jnp
    scan over KV blocks of `kv_block` keys (`_scan_attention`). Each runs
    under its `obs` scope.

    q: (B, Tq, H, hd); k,v: (B, Tk, KV, hd). q_offset: absolute position of
    q[0] relative to k[0] (for cached decode / chunked prefill).
    window: 0 = full; >0 = attend only to keys within `window` positions.
    scale: the scores' factor (None: hd^-1/2).
    """
    if ops.attention_fits(q, k, v, window=window, q_offset=q_offset):
        with jax.named_scope(obs.ATTN_KERNEL):
            return ops.attention(q, k, v, causal=causal, scale=scale)
    with jax.named_scope(obs.ATTN_JNP):
        return _scan_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, kv_block=kv_block,
                               scale=scale)


def _scan_attention(q, k, v, *, causal, window, q_offset, kv_block, scale):
    """Chunked online-softmax attention: a scan over KV blocks of
    `kv_block` keys, every block computed."""
    b, tq, h, hd = q.shape
    tk, n_kv = k.shape[1], k.shape[2]
    vd = v.shape[-1]
    g = h // n_kv
    scale = hd ** -0.5 if scale is None else scale
    qg = _gqa_expand(q, n_kv).astype(ACC) * scale       # (B,Tq,KV,G,hd)

    n_blocks = -(-tk // kv_block)
    pad = n_blocks * kv_block - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, n_blocks, kv_block, n_kv, hd)
    vb = v.reshape(b, n_blocks, kv_block, n_kv, vd)

    q_pos = q_offset + jnp.arange(tq)

    def step(carry, blk):
        m, l, acc = carry
        k_c, v_c, blk_idx = blk                          # (B,kb,KV,hd)
        k_pos = blk_idx * kv_block + jnp.arange(kv_block)
        s = jnp.einsum("btkgh,bskh->btkgs", qg, k_c.astype(ACC))
        mask = jnp.ones((tq, kv_block), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        mask &= (k_pos < tk)[None, :]
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "btkgs,bskh->btkgh", p, v_c.astype(ACC))
        return (m_new, l, acc), None

    init = (jnp.full((b, tq, n_kv, g), NEG_INF, ACC),
            jnp.zeros((b, tq, n_kv, g), ACC),
            jnp.zeros((b, tq, n_kv, g, vd), ACC))
    (m, l, acc), _ = jax.lax.scan(
        step, init, (kb.swapaxes(0, 1), vb.swapaxes(0, 1),
                     jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, tq, h, vd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_pos, pos, *, window=0):
    """Single-token attention over a (possibly ring-buffer) KV cache.

    q: (B, 1, H, hd); caches: (B, W, KV, hd); cache_pos: (B, W) absolute
    positions of cached entries (-1 = empty); pos: (B,) current position.
    Plain (non-chunked) formulation: the scores are (B,H,W), small for a
    single query. Grouped KV heads are read in place, without a copy.
    """
    b, _, h, hd = q.shape
    n_kv = k_cache.shape[2]
    g = h // n_kv
    qg = q.reshape(b, n_kv, g, hd).astype(ACC) * hd ** -0.5
    s = jnp.einsum("bkgh,bwkh->bkgw", qg, k_cache.astype(ACC))
    valid = (cache_pos >= 0) & (cache_pos <= pos[:, None])
    if window:
        valid &= cache_pos > pos[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgw,bwkh->bkgh", p, v_cache.astype(ACC))
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------

def attn_init(key, cfg, dtype):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _he(ks[0], (d, h * hd), dtype),
        "wk": _he(ks[1], (d, kv * hd), dtype),
        "wv": _he(ks[2], (d, kv * hd), dtype),
        "wo": _he(ks[3], (h * hd, d), dtype, fan_in=h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _proj(x, w, b=None):
    y = jnp.einsum("btd,df->btf", x, w, preferred_element_type=ACC)
    if b is not None:
        y = y + b.astype(ACC)
    return checkpoint_name(y.astype(x.dtype), PROJ)


def attn_qkv(p, cfg, x, positions):
    b, t, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = _proj(x, p["wq"], p.get("bq")).reshape(b, t, h, hd)
    k = _proj(x, p["wk"], p.get("bk")).reshape(b, t, kv, hd)
    v = _proj(x, p["wv"], p.get("bv")).reshape(b, t, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(p, o):
    b, t, h, hd = o.shape
    return _proj(o.reshape(b, t, h * hd), p["wo"])


def self_attention(p, cfg, x, positions, *, window=None):
    q, k, v = attn_qkv(p, cfg, x, positions)
    window = cfg.sliding_window if window is None else window
    o = flash_attention(q, k, v, causal=True, window=window)
    return attn_out(p, o)


def cross_attn_init(key, cfg, dtype):
    return attn_init(key, cfg, dtype)


def cross_attention(p, cfg, x, enc_kv):
    """enc_kv: precomputed (k, v) from encoder output."""
    b, t, _ = x.shape
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    q = _proj(x, p["wq"], p.get("bq")).reshape(b, t, h, hd)
    k, v = enc_kv
    o = flash_attention(q, k, v, causal=False)
    return attn_out(p, o)


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 Multi-head Latent Attention. Cache = compressed latent.
# ---------------------------------------------------------------------------

def mla_init(key, cfg, dtype):
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    return {
        "w_dq": _he(ks[0], (d, h * qk), dtype),
        "w_dkv": _he(ks[1], (d, m.kv_lora_rank), dtype),
        "w_kr": _he(ks[2], (d, m.qk_rope_dim), dtype),
        "w_uk": _he(ks[3], (m.kv_lora_rank, h * m.qk_nope_dim), dtype,
                    fan_in=m.kv_lora_rank),
        "w_uv": _he(ks[4], (m.kv_lora_rank, h * m.v_head_dim), dtype,
                    fan_in=m.kv_lora_rank),
        "wo": _he(ks[5], (h * m.v_head_dim, d), dtype, fan_in=h * m.v_head_dim),
        "kv_norm": rms_norm_init(m.kv_lora_rank, dtype),
    }


def mla_rope(x, positions, cfg):
    """RoPE on MLA's rope dims, YaRN-scaled where the configuration says."""
    y = cfg.yarn
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta,
                      freqs=yarn_freqs(x.shape[-1], cfg.rope_theta, y),
                      amp=yarn_mscale(y.factor, y.mscale)
                      / yarn_mscale(y.factor, y.mscale_all_dim))


def mla_scale(cfg):
    """MLA's softmax scale: qk_head_dim^-1/2, times mscale^2 under YaRN."""
    m, y = cfg.mla, cfg.yarn
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_latent(p, cfg, x, positions):
    """Compress x into the MLA cacheables: latent c_kv and shared rope key."""
    m = cfg.mla
    c_kv = rms_norm(p["kv_norm"], _proj(x, p["w_dkv"]), cfg.norm_eps)
    k_rope = _proj(x, p["w_kr"])[:, :, None, :]          # (B,T,1,rope)
    k_rope = mla_rope(k_rope, positions, cfg)
    return c_kv, k_rope[:, :, 0, :]


def mla_attention(p, cfg, x, positions, c_kv, k_rope, *, q_offset=0,
                  causal=True):
    """Attend queries from x over latent cache (c_kv, k_rope).

    c_kv: (B, S, r); k_rope: (B, S, rope). Keys/values are up-projected from
    the latent (the MLA trick: only r + rope dims are cached).
    """
    m, h = cfg.mla, cfg.n_heads
    b, t, _ = x.shape
    s = c_kv.shape[1]
    q = _proj(x, p["w_dq"]).reshape(b, t, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = mla_rope(q_rope, positions, cfg)
    k_nope = _proj(c_kv, p["w_uk"]).reshape(b, s, h, m.qk_nope_dim)
    v = _proj(c_kv, p["w_uv"]).reshape(b, s, h, m.v_head_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.qk_rope_dim))],
        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = flash_attention(q_full, k, v, causal=causal, q_offset=q_offset,
                        scale=mla_scale(cfg))
    return _proj(o.reshape(b, t, h * m.v_head_dim), p["wo"])


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d, d_ff, dtype):
    ks = jax.random.split(key, 3)
    return {"w_gate": _he(ks[0], (d, d_ff), dtype),
            "w_up": _he(ks[1], (d, d_ff), dtype),
            "w_down": _he(ks[2], (d_ff, d), dtype, fan_in=d_ff)}


def mlp(p, x):
    g = checkpoint_name(jnp.einsum("btd,df->btf", x, p["w_gate"],
                                   preferred_element_type=ACC), PROJ)
    u = checkpoint_name(jnp.einsum("btd,df->btf", x, p["w_up"],
                                   preferred_element_type=ACC), PROJ)
    y = jax.nn.silu(g) * u
    return jnp.einsum("btf,fd->btd", y.astype(x.dtype), p["w_down"],
                      preferred_element_type=ACC).astype(x.dtype)
