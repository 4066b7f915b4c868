"""Plain reference of the FedELMY paper CNN (appendix D.5), its weights and
data from the seed, and its operation counts.

NHWC inputs; three blocks of a SAME 3x3 conv, ReLU and a 2x2 max pool
(64, 128, 256 channels), then fc 4096 -> 256 -> 10 with a ReLU between.
The weight layout is the one the benchmark hands to both sides:
{"c1": {"w": (3, 3, C_in, C_out), "b": (C_out,)}, ..., "fc1": {"w", "b"},
"fc2": {"w", "b"}}. Convolutions are `lax.conv_general_dilated`; every
conv and matmul goes through `products.product`, in f32 at `HIGHEST`
unless a control asks for less.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.products import product

F32 = jnp.float32
CONVS = ("c1", "c2", "c3")


def init_params(shapes, key):
    """He-normal weights (fan-in = every axis but the last), zero biases,
    in the dtype of each shape, as one jitted call."""
    leaves, treedef = jax.tree.flatten(shapes)

    def make(key):
        out = []
        for i, s in enumerate(leaves):
            if len(s.shape) == 1:
                out.append(jnp.zeros(s.shape, s.dtype))
                continue
            fan_in = math.prod(s.shape[:-1])
            w = jax.random.normal(jax.random.fold_in(key, i), s.shape, F32)
            out.append((w / math.sqrt(fan_in)).astype(s.dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(key)


def make_data(config, traffic, key):
    """Per-client image shards in one jitted call: class-mean patterns
    (8x8x3, upsampled to 32x32) shared by all clients, each client's labels
    drawn from its own Dirichlet(label_beta) marginal, plus Gaussian noise.
    Returns [{"images": (n, 32, 32, 3) f32, "labels": (n,) i32}, ...]."""
    n_clients = traffic["clients"]
    n = traffic["samples_per_client"]
    classes = config["fc_widths"][-1]

    def make(key):
        k_means, k_mix, k_lab, k_noise = jax.random.split(key, 4)
        means = jax.random.normal(k_means, (classes, 8, 8, 3), F32)
        means = jnp.repeat(jnp.repeat(means, 4, axis=1), 4, axis=2)
        mix = jax.random.dirichlet(
            k_mix, jnp.full((classes,), traffic["label_beta"], F32),
            (n_clients,))
        labels = jax.random.categorical(
            k_lab, jnp.log(mix + 1e-9)[:, None, :], axis=-1,
            shape=(n_clients, n)).astype(jnp.int32)
        noise = jax.random.normal(k_noise, (n_clients, n, 32, 32, 3), F32)
        images = means[labels] + traffic["noise"] * noise
        return images, labels

    images, labels = jax.jit(make)(key)
    return [{"images": images[i], "labels": labels[i]}
            for i in range(n_clients)]


def _conv(x, w, b, mode):
    y = product(lambda x, w, prec: jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=prec), x, w, mode)
    return y + b.astype(F32)


def _dense(x, w, b, mode):
    y = product(lambda x, w, prec: jnp.dot(x, w, precision=prec), x, w, mode)
    return y + b.astype(F32)


def loss(config, params, batch, mode=None):
    """Mean softmax cross-entropy; `mode` as `products.product` takes it."""
    x = batch["images"].astype(F32)
    for name in CONVS:
        p = params[name]
        x = jax.nn.relu(_conv(x, p["w"], p["b"], mode))
        b, h, w, c = x.shape
        x = x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(_dense(x, params["fc1"]["w"], params["fc1"]["b"],
                           mode))
    logits = _dense(x, params["fc2"]["w"], params["fc2"]["b"], mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)
    return jnp.mean(lse - gold[:, 0])


def conv_gemms(config, batch):
    """The (M, K, N) of each conv as a GEMM at this batch: M = B*H*W output
    positions, K = 9*C_in, N = C_out; the spatial side halves per block."""
    side, c_in, out = config["image_side"], config["channels"], []
    for c_out in config["conv_widths"]:
        out.append((batch * side * side, 9 * c_in, c_out))
        side, c_in = side // 2, c_out
    return out


def forward_flops_per_sample(config):
    convs = sum(2 * m * k * n for m, k, n in conv_gemms(config, 1))
    dims = [config["conv_widths"][-1] * (config["image_side"] // 8) ** 2]
    dims += config["fc_widths"]
    fcs = sum(2 * a * b for a, b in zip(dims, dims[1:]))
    return convs + fcs


def train_flops_per_sample(config, traffic):
    """Forward, the weight gradients, and the input gradients of every
    layer but the first (no layer needs the image's gradient)."""
    m, k, n = conv_gemms(config, 1)[0]
    return 3 * forward_flops_per_sample(config) - 2 * m * k * n
