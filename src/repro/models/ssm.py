"""State-space sequence mixers: Mamba2 (SSD) and RWKV6 (Finch).

Both are instances of a *gated linear attention* recurrence over a matrix
state S ∈ R^{K×V} per head:

    S_t = D_t ⊙ S_{t-1} + k_tᵀ v_t          (D_t: decay, scalar or per-K-dim)
    y_t = q_t · S_t                           ("post" convention, Mamba2)
    y_t = q_t · (S_{t-1} + diag(u) k_tᵀ v_t)  ("pre" + bonus u, RWKV6)

Training/prefill uses a *chunked* formulation (lax.scan over chunks,
quadratic intra-chunk in pairwise log-decay-difference form — every exponent
is ≤ 0, so it is overflow-safe without FLA-style sub-chunking). Decode is the
plain one-step recurrence. The Pallas kernel `repro.kernels.chunk_scan` is
the TPU-target implementation of the intra-chunk block; this module is the
path every model lowers and the oracle's home.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import ACC, _he, rms_norm, rms_norm_init


# ---------------------------------------------------------------------------
# Core chunked GLA
# ---------------------------------------------------------------------------

def gla_chunked(q, k, v, log_decay, *, chunk: int, bonus=None,
                initial_state=None):
    """Chunked gated-linear-attention.

    q, k: (B, T, H, K); v: (B, T, H, V).
    log_decay: (B, T, H) scalar-per-head or (B, T, H, K) per-channel, ≤ 0.
    bonus: None → post convention (Mamba2); (H, K) → pre convention with
    current-token bonus (RWKV6).
    Returns y (B, T, H, V) and final state (B, H, K, V) in f32.
    """
    b, t, h, kd = q.shape
    vd = v.shape[-1]
    per_channel = log_decay.ndim == 4
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        # zero-pad: k=v=0 contributes nothing; log_decay=0 leaves the state
        # untouched, so the padded tail is inert
        pt = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, log_decay = pt(q), pt(k), pt(v), pt(log_decay)
        t = t + pad
    nc = t // chunk

    def r(x):  # (B,T,...) -> (NC, B, L, ...)
        return x.reshape(b, nc, chunk, *x.shape[2:]).swapaxes(0, 1)

    qc, kc, vc, ldc = r(q.astype(ACC)), r(k.astype(ACC)), r(v.astype(ACC)), \
        r(log_decay.astype(ACC))

    if initial_state is None:
        initial_state = jnp.zeros((b, h, kd, vd), ACC)

    pre = bonus is not None
    if pre:
        bonus = bonus.astype(ACC)

    idx = jnp.arange(chunk)
    tri_mask = idx[:, None] >= idx[None, :]          # j <= i
    strict_mask = idx[:, None] > idx[None, :]        # j <  i

    def chunk_step(S, xs):
        qx, kx, vx, ld = xs                          # (B,L,H,*) each
        if not per_channel:
            ld = ld[..., None]                       # (B,L,H,1)
        lc = jnp.cumsum(ld, axis=1)                  # inclusive
        lq = jnp.concatenate([jnp.zeros_like(lc[:, :1]), lc[:, :-1]], axis=1) \
            if pre else lc                           # exponent for the q side

        # ---- inter-chunk: y_i += (q_i ⊙ exp(lq_i)) · S -------------------
        q_eff = qx * jnp.exp(lq)
        y = jnp.einsum("blhk,bhkv->blhv", q_eff, S)

        # ---- intra-chunk -------------------------------------------------
        mask = strict_mask if pre else tri_mask
        if per_channel:
            # pairwise exponent (B,L,L,H,K): every entry ≤ 0
            ex = jnp.exp(jnp.where(mask[None, :, :, None, None],
                                   lq[:, :, None] - lc[:, None, :],
                                   -jnp.inf))
            s = jnp.einsum("blhk,bmhk,blmhk->blmh", qx, kx, ex)
        else:
            ex = jnp.exp(jnp.where(mask[None, :, :, None],
                                   lq[:, :, None, :, 0] - lc[:, None, :, :, 0],
                                   -jnp.inf))       # (B,L,L,H)
            s = jnp.einsum("blhk,bmhk->blmh", qx, kx) * ex
        y = y + jnp.einsum("blmh,bmhv->blhv", s, vx)
        if pre:
            y = y + jnp.einsum("blhk,hk,blhk,blhv->blhv",
                               qx, bonus, kx, vx)    # diag (current token)

        # ---- state update: S' = exp(lc_L) ⊙ S + Σ_j exp(lc_L−lc_j) k_jᵀv_j
        k_eff = kx * jnp.exp(lc[:, -1:] - lc)        # (B,L,H,K), exponents ≤ 0
        chunk_decay = jnp.exp(lc[:, -1])             # (B,H,K)
        S_new = S * chunk_decay[..., None] + jnp.einsum(
            "blhk,blhv->bhkv", k_eff, vx)
        return S_new, y

    S, ys = jax.lax.scan(chunk_step, initial_state, (qc, kc, vc, ldc))
    y = ys.swapaxes(0, 1).reshape(b, t, h, vd)
    if pad:
        y = y[:, :t - pad]
    return y.astype(v.dtype), S


def gla_step(q, k, v, log_decay, state, *, bonus=None):
    """One-token recurrence. q,k: (B,H,K); v: (B,H,V); state (B,H,K,V)."""
    q, k, v = q.astype(ACC), k.astype(ACC), v.astype(ACC)
    if log_decay.ndim == 2:                          # scalar per head
        log_decay = log_decay[..., None]
    d = jnp.exp(log_decay.astype(ACC))[..., None]    # (B,H,K,1)
    kv = k[..., None] * v[..., None, :]              # (B,H,K,V)
    if bonus is None:
        state = d * state + kv
        y = jnp.einsum("bhk,bhkv->bhv", q, state)
    else:
        y = jnp.einsum("bhk,bhkv->bhv", q,
                       state + bonus.astype(ACC)[None, :, :, None] * kv)
        state = d * state + kv
    return y.astype(v.dtype), state


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------

class Mamba2Dims(NamedTuple):
    d_inner: int
    n_heads: int
    head_dim: int
    state: int
    conv_width: int


def mamba2_dims(cfg) -> Mamba2Dims:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    return Mamba2Dims(d_inner, d_inner // s.head_dim, s.head_dim,
                      s.state_size, s.conv_width)


def mamba2_init(key, cfg, dtype):
    dm = mamba2_dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    conv_dim = dm.d_inner + 2 * dm.state
    return {
        # in_proj -> [z, x, B, C, dt]
        "w_in": _he(ks[0], (d, 2 * dm.d_inner + 2 * dm.state + dm.n_heads),
                    dtype),
        "conv_w": _he(ks[1], (dm.conv_width, conv_dim), dtype,
                      fan_in=dm.conv_width),
        "A_log": jnp.zeros((dm.n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((dm.n_heads,), jnp.float32),
        "D": jnp.ones((dm.n_heads,), jnp.float32),
        "norm": rms_norm_init(dm.d_inner, dtype),
        "w_out": _he(ks[2], (dm.d_inner, d), dtype, fan_in=dm.d_inner),
    }


def _causal_conv(x, w, state=None):
    """Depthwise causal conv. x: (B,T,C); w: (W,C); state: (B,W-1,C)|None."""
    width = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :].astype(x.dtype)
              for i in range(width))
    new_state = xp[:, -(width - 1):] if width > 1 else None
    return out, new_state


def _mamba2_qkvd(p, cfg, x, conv_state=None):
    dm = mamba2_dims(cfg)
    b, t, _ = x.shape
    proj = jnp.einsum("btd,df->btf", x, p["w_in"],
                      preferred_element_type=ACC).astype(x.dtype)
    z, xbc, dt = jnp.split(
        proj, [dm.d_inner, 2 * dm.d_inner + 2 * dm.state], axis=-1)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], conv_state)
    xbc = jax.nn.silu(xbc)
    xs, B, C = jnp.split(xbc, [dm.d_inner, dm.d_inner + dm.state], axis=-1)
    dt = jax.nn.softplus(dt.astype(ACC) + p["dt_bias"])           # (B,T,H)
    A = -jnp.exp(p["A_log"])                                      # (H,) < 0
    log_decay = dt * A                                            # ≤ 0
    xh = xs.reshape(b, t, dm.n_heads, dm.head_dim)
    k = jnp.broadcast_to(B[:, :, None, :], (b, t, dm.n_heads, dm.state))
    q = jnp.broadcast_to(C[:, :, None, :], (b, t, dm.n_heads, dm.state))
    v = (xh.astype(ACC) * dt[..., None]).astype(x.dtype)
    return q, k, v, log_decay, xh, z, new_conv


def mamba2_block(p, cfg, x):
    """Full-sequence Mamba2 mixer. x: (B,T,D) -> (B,T,D)."""
    dm = mamba2_dims(cfg)
    b, t, _ = x.shape
    q, k, v, log_decay, xh, z, _ = _mamba2_qkvd(p, cfg, x)
    y, _ = gla_chunked(q, k, v, log_decay, chunk=min(cfg.ssm.chunk_size, t))
    y = y + xh * p["D"][None, None, :, None].astype(x.dtype)
    y = y.reshape(b, t, dm.d_inner)
    y = rms_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return jnp.einsum("btf,fd->btd", y, p["w_out"],
                      preferred_element_type=ACC).astype(x.dtype)


def mamba2_decode(p, cfg, x, ssm_state, conv_state):
    """One-token step. x: (B,1,D); ssm_state: (B,H,N,P) f32."""
    dm = mamba2_dims(cfg)
    b = x.shape[0]
    q, k, v, log_decay, xh, z, new_conv = _mamba2_qkvd(p, cfg, x, conv_state)
    y, new_state = gla_step(q[:, 0], k[:, 0], v[:, 0], log_decay[:, 0],
                            ssm_state)
    y = y[:, None] + xh * p["D"][None, None, :, None].astype(x.dtype)
    y = y.reshape(b, 1, dm.d_inner)
    y = rms_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("btf,fd->btd", y, p["w_out"],
                     preferred_element_type=ACC).astype(x.dtype)
    return out, new_state, new_conv


# ---------------------------------------------------------------------------
# RWKV6 block (Finch): data-dependent per-channel decay via LoRA.
# ---------------------------------------------------------------------------

RWKV_LORA = 64


def rwkv6_init(key, cfg, dtype):
    d = cfg.d_model
    s = cfg.ssm
    n_heads = d // s.head_dim
    ks = jax.random.split(key, 9)
    return {
        "mix": 0.5 * jnp.ones((5, d), dtype),        # token-shift lerp r,k,v,w,g
        "w_r": _he(ks[0], (d, d), dtype),
        "w_k": _he(ks[1], (d, d), dtype),
        "w_v": _he(ks[2], (d, d), dtype),
        "w_g": _he(ks[3], (d, d), dtype),
        "w_o": _he(ks[4], (d, d), dtype),
        "w_decay_base": jnp.full((d,), -2.0, jnp.float32),
        "w_lora_a": _he(ks[5], (d, RWKV_LORA), dtype),
        "w_lora_b": (jax.random.normal(ks[6], (RWKV_LORA, d)) * 0.01
                     ).astype(dtype),
        "bonus_u": jnp.zeros((n_heads, s.head_dim), jnp.float32),
        "ln_x": rms_norm_init(d, dtype),
    }


def _rwkv6_inputs(p, cfg, x, x_prev):
    """x: (B,T,D); x_prev: (B,1,D) last token of previous segment."""
    s = cfg.ssm
    d = cfg.d_model
    b, t, _ = x.shape
    h = d // s.head_dim
    shifted = jnp.concatenate([x_prev.astype(x.dtype), x[:, :-1]], axis=1)
    mix = p["mix"].astype(ACC)
    xf, sf = x.astype(ACC), shifted.astype(ACC)
    mixed = [xf * mix[i] + sf * (1 - mix[i]) for i in range(5)]
    mr, mk, mv, mw, mg = [m.astype(x.dtype) for m in mixed]
    proj = lambda z, w: jnp.einsum("btd,df->btf", z, w,
                                   preferred_element_type=ACC).astype(x.dtype)
    r = proj(mr, p["w_r"]).reshape(b, t, h, s.head_dim)
    k = proj(mk, p["w_k"]).reshape(b, t, h, s.head_dim)
    v = proj(mv, p["w_v"]).reshape(b, t, h, s.head_dim)
    g = jax.nn.silu(proj(mg, p["w_g"]))
    # data-dependent decay (the Finch contribution): w = -exp(base + lora)
    lora = jnp.einsum("btd,dr,rf->btf", jnp.tanh(mw.astype(ACC)),
                      p["w_lora_a"].astype(ACC), p["w_lora_b"].astype(ACC))
    log_decay = -jnp.exp(p["w_decay_base"] + lora)               # (B,T,D) ≤ 0
    log_decay = log_decay.reshape(b, t, h, s.head_dim)
    return r, k, v, g, log_decay, x[:, -1:]


def rwkv6_block(p, cfg, x, x_prev=None):
    b, t, d = x.shape
    if x_prev is None:
        x_prev = jnp.zeros_like(x[:, :1])
    r, k, v, g, log_decay, _ = _rwkv6_inputs(p, cfg, x, x_prev)
    y, _ = gla_chunked(r, k, v, log_decay,
                       chunk=min(32, t), bonus=jnp.exp(p["bonus_u"]))
    y = rms_norm(p["ln_x"], y.reshape(b, t, d), cfg.norm_eps) * g
    return jnp.einsum("btd,df->btf", y, p["w_o"],
                      preferred_element_type=ACC).astype(x.dtype)


def rwkv6_decode(p, cfg, x, state, x_prev):
    """x: (B,1,D); state: (B,H,K,V) f32; x_prev: (B,1,D)."""
    b, _, d = x.shape
    r, k, v, g, log_decay, new_prev = _rwkv6_inputs(p, cfg, x, x_prev)
    y, new_state = gla_step(r[:, 0], k[:, 0], v[:, 0], log_decay[:, 0], state,
                            bonus=jnp.exp(p["bonus_u"]))
    y = rms_norm(p["ln_x"], y.reshape(b, 1, d), cfg.norm_eps) * g
    out = jnp.einsum("btd,df->btf", y, p["w_o"],
                     preferred_element_type=ACC).astype(x.dtype)
    return out, new_state, new_prev
