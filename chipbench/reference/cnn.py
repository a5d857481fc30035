"""Plain reference of the paper's CNN (section V): two 5x5 convolutions
with 10 and 20 channels, each followed by ReLU and 2x2 max pooling, then
fully connected layers 320-120-84-10 with ReLU between them.

Written from the paper's description in straightforward ``jax.numpy``:
convolutions and products at HIGHEST precision, pooling by reshape and
max. The feature extractor (the paper's omega^f) is the two
convolutions under ``body``; the classifier (omega^c) is fc1-fc3.
``dtype`` sets the precision of weights, activations and products; the
configuration states float32, and the control runs it in bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BODY = ("body",)


def param_specs(cfg: dict) -> dict:
    """Initial-weight specs (see ``chipbench.gen.weights``)."""
    s = cfg["sizes"]
    k, cin = s["kernel"], s["image_shape"][-1]
    c1, c2 = s["conv_channels"]
    fc = s["fc"]
    dt = cfg["dtype"]
    spec = {"body": {"conv1": {"w": ((k, k, cin, c1), "normal", 0.1, dt)},
                     "conv2": {"w": ((k, k, c1, c2), "normal", 0.1, dt)}}}
    for i, (d_in, d_out) in enumerate(zip(fc[:-1], fc[1:]), start=1):
        spec[f"fc{i}"] = {"w": ((d_in, d_out), "uniform", d_in ** -0.5, dt),
                          "b": ((d_out,), "zeros", 0.0, dt)}
    return spec


def output_bias(cfg: dict) -> tuple:
    """Path of the output layer's bias in the weights."""
    return (f"fc{len(cfg['sizes']['fc']) - 1}", "b")


def _conv_relu_pool(x, w):
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    y = jnp.maximum(y, 0)
    b, h, wd, c = y.shape
    return y.reshape(b, h // 2, 2, wd // 2, 2, c).max(axis=(2, 4))


def logits(params, images, dtype=jnp.float32):
    x = images.astype(dtype)
    x = _conv_relu_pool(x, params["body"]["conv1"]["w"])
    x = _conv_relu_pool(x, params["body"]["conv2"]["w"])
    x = x.reshape(x.shape[0], -1)
    n_fc = sum(1 for k in params if k.startswith("fc"))
    for i in range(1, n_fc + 1):
        p = params[f"fc{i}"]
        x = jnp.dot(x, p["w"].astype(dtype), precision=HIGHEST) + p["b"]
        if i < n_fc:
            x = jnp.maximum(x, 0)
    return x


def nll(params, images, labels, dtype=jnp.float32):
    """Per-example negative log-likelihood, in f32 from ``dtype`` logits."""
    z = logits(params, images, dtype).astype(jnp.float32)
    gold = jnp.take_along_axis(z, labels[:, None], axis=1)[:, 0]
    return jax.nn.logsumexp(z, axis=1) - gold


def loss(params, batch, dtype=jnp.float32):
    return jnp.mean(nll(params, batch["image"], batch["label"], dtype))


def is_body(path) -> bool:
    """True for leaves of the feature extractor (frozen under FES)."""
    return path[0].key in BODY
