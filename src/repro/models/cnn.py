"""The paper's own task model: 2 conv (5x5) + 3 FC layers for 28x28 images.

This is the model AMA-FES is evaluated on (MNIST / FMNIST, Section V).
FES split is exactly the paper's: feature extractor = the conv layers,
classifier = the three FC layers ("all the computing-limited devices ...
train only the final three FC layers").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import cross_entropy_loss, dense, dense_init


def init_params(cfg, key):
    ks = jax.random.split(key, 5)
    c1, c2 = 10, 20
    p = {
        # feature extractor (conv) — paper's omega^f
        "body": {
            "conv1": {"w": 0.1 * jax.random.normal(ks[0], (5, 5, 1, c1))},
            "conv2": {"w": 0.1 * jax.random.normal(ks[1], (5, 5, c1, c2))},
        },
        # classifier (3 FC) — paper's omega^c
        "fc1": dense_init(ks[2], 4 * 4 * c2, 120, jnp.float32, bias=True),
        "fc2": dense_init(ks[3], 120, 84, jnp.float32, bias=True),
        "fc3": dense_init(ks[4], 84, cfg.vocab_size, jnp.float32, bias=True),
    }
    return p


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _filter_grad(x, g, kh, kw):
    """The (kh, kw, C, O) filter gradient of a VALID, stride-1
    convolution of (B, H, W, C) ``x`` whose output gradient is
    (B, Ho, Wo, O) ``g``: dW[i, j] = sum_{b,y,x} x[b, y+i, x+j] g[b, y, x].

    One dot of the input's kh row slices with ``g``, contracting batch
    and output rows, gives every (input column, output column) pair for
    each filter row; tap j is the diagonal input column = output column
    + j, summed out by a 0/1 mask, which is exact in f32. Slices and a
    dot, no patch-extracting convolution: that would bring back the op
    this replaces. The kh row slices hold kh copies of the input, where
    all kh·kw patches would hold kh·kw, with the taps padded out on the
    TPU's 128-lane minor axis.
    """
    w = x.shape[2]
    ho, wo = g.shape[1:3]
    rows = jnp.stack([x[:, i:i + ho] for i in range(kh)])     # (kh,B,Ho,W,C)
    a = jax.lax.dot_general(rows, g, (((1, 2), (0, 1)), ((), ())))
    diag = jnp.asarray(np.arange(w)[:, None, None] == np.arange(wo)[:, None]
                       + np.arange(kw), a.dtype)               # (W, Wo, kw)
    dw = jnp.sum(a[:, :, :, :, None, :] * diag[None, :, None, :, :, None],
                 axis=(1, 3))                                  # (kh,C,kw,O)
    return dw.transpose(0, 2, 1, 3)


@jax.custom_vjp
def conv_valid(x, w):
    """VALID, stride-1 convolution of NHWC ``x`` with an HWIO filter.

    The forward is ``lax.conv_general_dilated`` itself. The filter's
    gradient is a dot of the input's row slices with the output's
    gradient (``_filter_grad``): under the clients' vmap a batched dot
    with the clients as its batch dimension. Autodiff of the convolution
    gives a grouped convolution there instead (the clients folded into
    the channels, the whole output gradient as its window), which a TPU
    runs many times slower than the work needs. The input's gradient is
    lax's own transposed convolution; where the input is data nothing
    asks for it, and the lowered program has none.
    """
    return _conv(x, w)


def _conv_fwd(x, w):
    return _conv(x, w), (x, w)


def _conv_bwd(res, g):
    x, w = res
    (dx,) = jax.vjp(lambda v: _conv(v, w), x)[1](g)
    return dx, _filter_grad(x, g, *w.shape[:2])


conv_valid.defvjp(_conv_fwd, _conv_bwd)


def _corners(x):
    """The four (..., H/2, W/2, C) corners of the 2x2 windows of
    (..., H, W, C), in row-major window order: (0,0), (0,1), (1,0), (1,1).
    Taken from a (..., H/2, 2, W/2, 2, C) view, not by strided slices,
    which lower to extra layout copies on a TPU."""
    *lead, h, w, c = x.shape
    if h % 2 or w % 2:
        raise ValueError(f"2x2 pooling needs even H and W, got {h}x{w}")
    win = x.reshape(*lead, h // 2, 2, w // 2, 2, c)
    return (win[..., 0, :, 0, :], win[..., 0, :, 1, :],
            win[..., 1, :, 0, :], win[..., 1, :, 1, :])


@jax.custom_vjp
def max_pool_2x2(x):
    """2x2 / stride-2 max-pool of (..., H, W, C) with even H and W.

    Equal to ``lax.reduce_window(max)`` bit for bit, and so is its
    gradient: each window's output gradient goes to the first position,
    in row-major window order, that holds the maximum, as the
    ``select_and_scatter`` gradient of ``reduce_window`` routes it
    (autodiff of a plain max would split a tie). Both directions are
    elementwise ops on the windows' corners, which XLA fuses on a TPU;
    ``reduce_window`` and ``select_and_scatter`` are slow there.
    """
    a, b, c, d = _corners(x)
    return jnp.maximum(jnp.maximum(jnp.maximum(a, b), c), d)


def _max_pool_fwd(x):
    y = max_pool_2x2(x)
    return y, (x, y)


def _max_pool_bwd(res, g):
    x, y = res
    taken = jnp.zeros(y.shape, bool)
    grads = []
    for corner in _corners(x):
        hit = (corner == y) & ~taken
        grads.append(jnp.where(hit, g, jnp.zeros((), g.dtype)))
        taken = taken | hit
    # back to (..., H/2, 2, W/2, 2, C): each window row, then the two rows
    top = jnp.stack(grads[:2], axis=-2)
    bottom = jnp.stack(grads[2:], axis=-2)
    return (jnp.stack([top, bottom], axis=-4).reshape(x.shape),)


max_pool_2x2.defvjp(_max_pool_fwd, _max_pool_bwd)


def forward(params, cfg, batch):
    """batch: {"image": (B, 28, 28, 1)} -> logits (B, n_classes)."""
    x = batch["image"].astype(jnp.float32)
    x = jax.nn.relu(conv_valid(x, params["body"]["conv1"]["w"]))  # (B,24,24,10)
    x = max_pool_2x2(x)
    x = jax.nn.relu(_conv(x, params["body"]["conv2"]["w"]))     # (B,8,8,20)
    x = max_pool_2x2(x)
    x = x.reshape(x.shape[0], -1)                               # (B, 320)
    x = jax.nn.relu(dense(params["fc1"], x))
    x = jax.nn.relu(dense(params["fc2"], x))
    return dense(params["fc3"], x), jnp.float32(0.0)


def loss_fn(params, cfg, batch):
    logits, _ = forward(params, cfg, batch)
    return cross_entropy_loss(logits, batch["label"])


def accuracy(params, cfg, batch):
    logits, _ = forward(params, cfg, batch)
    return jnp.mean((jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
