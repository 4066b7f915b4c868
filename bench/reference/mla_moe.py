"""Plain reference of DeepSeek-V2's decoder (arXiv:2405.04434) as one chip
of an expert-parallel group holds it: MLA with YaRN RoPE, leading dense
SwiGLU layers, then MoE layers of which this chip computes its held
experts' part, with the shared experts and the per-sequence balance loss.
Also its weights and data from the seed (`decoder.py`'s), its operation
count, and the operations and bytes of the program's grouped GEMM.

Per layer, with h = RMSNorm(x) (eps `rms_norm_eps`), H heads:

    q = h W_dq, split per head into q_nope (128) and q_rope (64)
    c = RMSNorm(h W_dkv) (the latent, 512), k_r = h W_kr (64, one for all
    heads); k_nope = c W_uk, v = c W_uv (per head 128 and 128)
    q_rope, k_r rotated by YaRN RoPE; k = [k_nope, k_r] on every head
    x += W_o softmax(s * q k^T + causal mask) v, s = 192^-1/2 * mscale^2

YaRN (`rope_scaling`: factor F, original length L0, beta_fast, beta_slow,
mscale, mscale_all_dim; theta; rope dims D): pair i's inverse frequency is

    f_i = theta^(-2i/D),  r_i = clip((i - lo) / (hi - lo), 0, 1)
    inv_freq_i = f_i / F * r_i + f_i * (1 - r_i)
    lo = floor(d(beta_fast)), hi = ceil(d(beta_slow)) (clipped to [0, D-1]),
    d(beta) = D ln(L0 / (2 pi beta)) / (2 ln theta)
    cos, sin times m(F, mscale) / m(F, mscale_all_dim),
    m(F, a) = 0.1 a ln F + 1, and mscale = m(F, mscale_all_dim).

Then x += W_down(silu(W_gate h2) * W_up h2), h2 = RMSNorm(x): the first
`first_k_dense_replace` layers at `intermediate_size`; after them the MoE
layer. Its router is f32 over all `router_outputs` experts: P = softmax(h2
W_r); each token's experts are the greedy top-k of P, its gates their
probabilities (divided by their sum only where `norm_topk_prob`), times
`routed_scaling_factor`. This chip holds experts [0, `n_routed_experts`)
and adds, for each held expert e, its SwiGLU of h2 weighted by the gate
of the tokens that chose e (0 elsewhere); the `n_shared_experts` shared
experts are one SwiGLU of width n_shared x `moe_intermediate_size`, added
once. The balance loss, per sequence b of T tokens, over all E experts:

    aux = alpha * mean_b sum_e (E / (T k)) count_{b,e} * mean_t P_{b,t,e}

The loss is the mean next-token cross-entropy over the (sliced)
vocabulary, through an untied head after a final RMSNorm, plus every MoE
layer's aux.

Departures, both weight permutations of the published math: RoPE rotates
the two halves of the rope dims (the published code interleaves them and
permutes before rotating); `kv_a_proj_with_mqa` is split into W_dkv and
W_kr, and `kv_b_proj` into W_uk and W_uv.

The weight layout is the program's: "embed" (V, d), "final_norm",
"lm_head" (d, V), and stacks "dense_layers" and "layers" with "ln1",
"ln2", "attn" {"w_dq", "w_dkv", "w_kr", "w_uk", "w_uv", "wo", "kv_norm"}
and "ffn": {"w_gate", "w_up", "w_down"} for a dense layer; {"router" (d,
E) f32, "w_gate", "w_up" (H, d, f), "w_down" (H, f, d), "shared" {...}}
for a MoE layer. Everything is f32 from the stored weights, every product
through `products.product`; each layer and each block of query rows is
recomputed in the backward pass.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.decoder import (Q_BLOCK, _mm, _rms, init_params,
                                     make_data)

F32 = jnp.float32
__all__ = ["init_params", "make_data", "logits", "loss",
           "train_flops_per_sample", "expert_gemm_work"]


def _mscale(factor, a):
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    dim, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]

    def d(beta):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (2 * math.pi * beta)) / (2 * math.log(theta))

    lo = max(math.floor(d(rs["beta_fast"])), 0)
    hi = min(math.ceil(d(rs["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    i = jnp.arange(dim // 2, dtype=F32)
    f = 1.0 / theta ** (2 * i / dim)
    r = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return f / rs["factor"] * r + f * (1.0 - r)


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, cfg):
    """x (B, T, ..., D): YaRN rotation of the two halves."""
    rs = cfg["rope_scaling"]
    amp = _mscale(rs["factor"], rs["mscale"]) / _mscale(
        rs["factor"], rs["mscale_all_dim"])
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * yarn_inv_freq(cfg)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * amp).reshape(shape)
    sin = (jnp.sin(ang) * amp).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, scale, mode):
    """q, k (B, T, H, 192), v (B, T, H, 128) -> (B, T, H, 128), causal,
    in blocks of query rows."""
    b, t, h, _ = q.shape
    pos = jnp.arange(t)

    @jax.checkpoint
    def block(qb, start):
        s = _mm("bqhd,bkhd->bhqk", qb, k, mode) * scale
        rows = start + jnp.arange(qb.shape[1])
        s = jnp.where(rows[:, None] >= pos[None, :], s, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, mode)

    nb = t // Q_BLOCK
    qs = q.reshape(b, nb, Q_BLOCK, h, q.shape[-1]).swapaxes(0, 1)
    out = jax.lax.map(lambda a: block(a[0], a[1]),
                      (qs, jnp.arange(nb) * Q_BLOCK))
    return out.swapaxes(0, 1).reshape(b, t, h, v.shape[-1])


def _mla(cfg, a, h, mode):
    b, t, _ = h.shape
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q = _mm("btd,df->btf", h, a["w_dq"], mode).reshape(b, t, heads,
                                                       nope + rope)
    c = _rms(_mm("btd,dr->btr", h, a["w_dkv"], mode), a["kv_norm"]["scale"],
             cfg["rms_norm_eps"])
    k_r = _rope(_mm("btd,dr->btr", h, a["w_kr"], mode), cfg)
    k_nope = _mm("btr,rf->btf", c, a["w_uk"], mode).reshape(b, t, heads, nope)
    v = _mm("btr,rf->btf", c, a["w_uv"], mode).reshape(b, t, heads, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_r[:, :, None, :], (b, t, heads, rope))], -1)
    o = _attention(q, k, v, softmax_scale(cfg), mode)
    return _mm("btf,fd->btd", o.reshape(b, t, -1), a["wo"], mode)


def _swiglu(h, f, mode):
    return _mm("btf,fd->btd",
               jax.nn.silu(_mm("btd,df->btf", h, f["w_gate"], mode))
               * _mm("btd,df->btf", h, f["w_up"], mode), f["w_down"], mode)


def _moe(cfg, f, h, mode):
    """-> (held experts' part + shared experts, balance loss)."""
    b, t, _ = h.shape
    n_exp, k = cfg["router_outputs"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm("btd,de->bte", h, f["router"], mode), -1)
    gates, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * cfg["routed_scaling_factor"]
    y = _swiglu(h, f["shared"], mode)
    for e in range(f["w_gate"].shape[0]):
        w = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)      # (B, T)
        y = y + w[..., None] * _swiglu(
            h, {n: f[n][e] for n in ("w_gate", "w_up", "w_down")}, mode)
    count = jnp.sum(jax.nn.one_hot(idx, n_exp, dtype=F32), axis=(1, 2))
    aux = cfg["aux_loss_alpha"] * jnp.mean(jnp.sum(
        count * (n_exp / (t * k)) * probs.mean(1), -1))
    return y, aux


def _final(cfg, params, x, mode):
    h = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm("btd,dv->btv", h, params["lm_head"], mode)


def _backbone(cfg, params, tokens, mode):
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    aux_total = jnp.zeros((), F32)

    def layer(x, lp):
        x = x + _mla(cfg, lp["attn"], _rms(x, lp["ln1"]["scale"], eps), mode)
        h2 = _rms(x, lp["ln2"]["scale"], eps)
        if "router" in lp["ffn"]:
            y, aux = _moe(cfg, lp["ffn"], h2, mode)
        else:
            y, aux = _swiglu(h2, lp["ffn"], mode), jnp.zeros((), F32)
        return x + y, aux

    layer = jax.checkpoint(layer)
    for name in ("dense_layers", "layers"):
        stack = params[name]
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            x, aux = layer(x, jax.tree.map(lambda a: a[i], stack))
            aux_total = aux_total + aux
    return x, aux_total


def logits(cfg, params, tokens, mode=None):
    """(B, T) token ids -> (B, T, V) f32 logits."""
    return _final(cfg, params, _backbone(cfg, params, tokens, mode)[0], mode)


def loss(cfg, params, batch, mode=None):
    x, aux = _backbone(cfg, params, batch["tokens"], mode)
    out = _final(cfg, params, x, mode)
    lse = jax.nn.logsumexp(out, axis=-1)
    gold = jnp.take_along_axis(out, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - gold) + aux


# -- operation counts ----------------------------------------------------------

def forward_flops_per_token(config, seq_len):
    """2 x the matrices a token passes through, the routed experts at the
    nominal share k x held / E of one expert each, plus attention's two
    products over the causal half."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    r, vd = config["kv_lora_rank"], config["v_head_dim"]
    n_layers, n_dense = (config["num_hidden_layers"],
                         config["first_k_dense_replace"])
    f = config["moe_intermediate_size"]
    attn = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) \
        + h * vd * d
    share = (config["num_experts_per_tok"] * config["n_routed_experts"]
             / config["router_outputs"])
    moe = d * config["router_outputs"] + 3 * d * f * (
        config["n_shared_experts"] + share)
    dense = 3 * d * config["intermediate_size"]
    matrices = n_layers * attn + n_dense * dense + (n_layers - n_dense) * moe \
        + d * config["vocab_size"]
    scores = n_layers * 2 * h * (nope + rope + vd) * (seq_len / 2)
    return 2 * matrices + scores


def train_flops_per_sample(config, traffic):
    """Per packed sequence: forward, and the backward at twice the
    forward, per token (recomputation not counted)."""
    t = traffic["seq_len"]
    return 3 * t * forward_flops_per_token(config, t)


def expert_gemm_work(config, rows: float):
    """(operations, bytes) of one call of the held experts' grouped GEMM
    over `rows` assigned rows: every call (gate, up and down forward; the
    input and weight gradients) is 2 x rows x d x f operations and reads
    its rows, the held experts' weight panels and writes its result once,
    at 2 bytes an element."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    return 2.0 * rows * d * f, 2.0 * (rows * (d + f) + held * d * f)
