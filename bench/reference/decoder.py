"""Plain reference of a dense GQA decoder (Llama-style blocks, as Granite
Code uses them), its weights and packed token data from the seed, and its
operation counts.

Per layer: x += Wo(attn(RoPE(Wq h + bq), RoPE(Wk h + bk), Wv h + bv))
with h = RMSNorm(x) (the q, k, v biases where the weights hold them); x += W_down(silu(W_gate h2) * W_up h2) with h2 = RMSNorm(x).
Causal softmax attention over grouped KV heads (query head i reads KV head
i // (heads / kv_heads)), scaled by head_dim^-1/2; RoPE rotates the two
halves of each head (theta from the configuration). Then RMSNorm, an
untied head, and the mean next-token cross-entropy.

The weight layout is the one the benchmark hands to both sides: "embed"
(V, d), "final_norm" {"scale"}, "lm_head" (d, V), and "layers" stacked on
a leading L axis: "ln1"/"ln2" {"scale"}, "attn" {"wq", "wk", "wv", "wo",
and "bq", "bk", "bv" where the configuration has biases}, "ffn" {"w_gate",
"w_up", "w_down"}. Everything is computed in f32 from the stored weights;
every product goes through `products.product`, in f32 at `HIGHEST` unless
a control asks for less. Each layer, and each block of query rows in it, is
recomputed in the backward pass, so the reference fits beside its
optimizer state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.products import product

F32 = jnp.float32
Q_BLOCK = 256


def init_params(shapes, key):
    """Embedding and biases N(0, 0.02), norm scales 1, every other matrix
    N(0, 1/fan_in) with fan_in its second-to-last axis; in each shape's
    dtype, as one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, s) in enumerate(flat):
            names = [getattr(p, "key", "") for p in path]
            k = jax.random.fold_in(key, i)
            if "scale" in names:
                out.append(jnp.ones(s.shape, s.dtype))
            elif "embed" in names or names[-1] in ("bq", "bk", "bv"):
                out.append((0.02 * jax.random.normal(k, s.shape, F32)
                            ).astype(s.dtype))
            else:
                out.append((jax.random.normal(k, s.shape, F32)
                            / jnp.sqrt(float(s.shape[-2]))).astype(s.dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(key)


def make_data(config, traffic, key):
    """Packed sequences of one silo's corpus, as a sparse Markov chain
    over the vocabulary (each token has `markov_successors` successors
    with Dirichlet(`markov_alpha`) weights), in one jitted call. Returns
    [{"tokens": (n, T) i32, "labels": (n, T) i32}] per client."""
    v, n, t = (config["vocab_size"], traffic["seqs_per_client"],
               traffic["seq_len"])
    k_succ = traffic["markov_successors"]

    def client(key):
        k_next, k_w, k_0, k_walk = jax.random.split(key, 4)
        succ = jax.random.randint(k_next, (v, k_succ), 0, v, jnp.int32)
        logw = jnp.log(jax.random.dirichlet(
            k_w, jnp.full((k_succ,), traffic["markov_alpha"], F32), (v,))
            + 1e-9)
        state = jax.random.randint(k_0, (n,), 0, v, jnp.int32)

        def step(state, k):
            pick = jax.random.categorical(k, logw[state], axis=-1)
            nxt = succ[state, pick]
            return nxt, nxt

        _, rest = jax.lax.scan(step, state, jax.random.split(k_walk, t))
        seqs = jnp.concatenate([state[None], rest], axis=0).T   # (n, T+1)
        return seqs[:, :-1], seqs[:, 1:]

    keys = jax.random.split(key, traffic["clients"])
    out = jax.jit(jax.vmap(client))(keys)
    return [{"tokens": out[0][i], "labels": out[1][i]}
            for i in range(traffic["clients"])]


def _mm(eq, a, b, mode):
    return product(lambda a, b, prec: jnp.einsum(eq, a, b, precision=prec),
                   a, b, mode)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    t, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, mode):
    """q (B, T, H, hd); k, v (B, T, KV, hd) -> (B, T, H, hd), causal, in
    blocks of query rows."""
    b, t, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    pos = jnp.arange(t)

    @jax.checkpoint
    def block(qb, start):
        s = _mm("bqhd,bkhd->bhqk", qb, k, mode) * hd ** -0.5
        rows = start + jnp.arange(qb.shape[1])
        s = jnp.where(rows[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, mode)

    nb = t // Q_BLOCK
    qs = q.reshape(b, nb, Q_BLOCK, h, hd).swapaxes(0, 1)
    out = jax.lax.map(lambda a: block(a[0], a[1]),
                      (qs, jnp.arange(nb) * Q_BLOCK))
    return out.swapaxes(0, 1).reshape(b, t, h, hd)


def logits(cfg, params, tokens, mode=None):
    """(B, T) token ids -> (B, T, V) f32 logits."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    b, t = tokens.shape
    x = params["embed"][tokens].astype(F32)

    @jax.checkpoint
    def layer(x, lp):
        a, f = lp["attn"], lp["ffn"]
        h = _rms(x, lp["ln1"]["scale"], eps)
        def proj(w, bias, n):
            y = _mm("btd,df->btf", h, a[w], mode)
            if bias in a:
                y = y + a[bias].astype(F32)
            return y.reshape(b, t, n, hd)

        q, k, v = proj("wq", "bq", heads), proj("wk", "bk", kv), \
            proj("wv", "bv", kv)
        o = _attention(_rope(q, theta), _rope(k, theta), v, mode)
        x = x + _mm("btf,fd->btd", o.reshape(b, t, heads * hd), a["wo"],
                    mode)
        h2 = _rms(x, lp["ln2"]["scale"], eps)
        y = jax.nn.silu(_mm("btd,df->btf", h2, f["w_gate"], mode)) \
            * _mm("btd,df->btf", h2, f["w_up"], mode)
        return x + _mm("btf,fd->btd", y, f["w_down"], mode)

    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
    h = _rms(x, params["final_norm"]["scale"], eps)
    return _mm("btd,dv->btv", h, params["lm_head"], mode)


def loss(cfg, params, batch, mode=None):
    out = logits(cfg, params, batch["tokens"], mode)
    lse = jax.nn.logsumexp(out, axis=-1)
    gold = jnp.take_along_axis(out, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def matrix_params(config):
    """Parameters of the layers' matrices and the head (no embedding; the
    biases' additions are not counted)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    h, kv, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def forward_flops_per_token(config, seq_len):
    """2 x the matrices, plus attention's two products over the causal
    half: 2 x 2 x heads x head_dim x T/2 per layer."""
    attn = config["num_hidden_layers"] * 2 * 2 * config[
        "num_attention_heads"] * config["head_dim"] * (seq_len / 2)
    return 2 * matrix_params(config) + attn


def train_flops_per_sample(config, traffic):
    """Per packed sequence: forward, and the backward at twice the
    forward, per token (recomputation not counted)."""
    t = traffic["seq_len"]
    return 3 * t * forward_flops_per_token(config, t)
