"""Plain reference of one synchronous AMA-FES federated round (paper
Algorithm 1 with Eqs. 2, 3 and 5).

Each selected client runs local SGD from the global weights over its
staged batches. A computing-limited client (FES) takes no step on the
feature extractor: its gradient there is zero, so only the classifier
moves. The server then mixes (Eq. 5):

    w_i     = |D_i| / sum_j |D_j|       over the clients that arrived
    alpha_t = min(alpha0 + eta * t, alpha_cap)
    omega_t = alpha_t * omega_{t-1} + (1 - alpha_t) * sum_i w_i omega_ti

The round's loss is the mean over clients of each client's mean loss
over its local steps, each taken before the step's update. Clients run
one after another; each is one ``lax.scan`` over its steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import cnn


@functools.partial(jax.jit, static_argnames=("dtype", "lr"))
def local_sgd(params, images, labels, limited, *, lr: float, dtype):
    """images (steps, b, ...), labels (steps, b) -> (params, mean loss)."""
    grad = jax.value_and_grad(lambda p, x, y: cnn.loss(
        p, {"image": x, "label": y}, dtype))

    def step(p, xy):
        loss, g = grad(p, *xy)
        g = jax.tree_util.tree_map_with_path(
            lambda path, gi: jnp.where(limited & cnn.is_body(path),
                                       jnp.zeros_like(gi), gi), g)
        p = jax.tree.map(lambda pi, gi: (pi - lr * gi).astype(pi.dtype),
                         p, g)
        return p, loss

    params, losses = jax.lax.scan(step, params, (images, labels))
    return params, jnp.mean(losses)


def ama_mix(prev, clients: list, sizes: np.ndarray, t: int, fl: dict):
    w = np.asarray(sizes, np.float64) / np.sum(sizes)
    alpha = min(fl["alpha0"] + fl["eta"] * t, fl["alpha_cap"])

    def mix(p, *cs):
        agg = sum(float(wi) * c.astype(jnp.float32) for wi, c in zip(w, cs))
        return (alpha * p.astype(jnp.float32)
                + (1.0 - alpha) * agg).astype(p.dtype)

    return jax.tree.map(mix, prev, *clients)


def fl_round(params, t: int, images, labels, limited, sizes, fl: dict,
             dtype=jnp.float32):
    """images (C, steps, b, ...) for the round's selected clients.
    -> (new global params, round loss)."""
    outs, losses = [], []
    for c in range(images.shape[0]):
        p, l = local_sgd(params, images[c], labels[c], bool(limited[c]),
                         lr=float(fl["lr"]), dtype=dtype)
        outs.append(p)
        losses.append(float(l))
    return ama_mix(params, outs, sizes, t, fl), float(np.mean(losses))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _eval_block(params, images, labels, *, dtype):
    z = cnn.logits(params, images, dtype).astype(jnp.float32)
    nll = jax.nn.logsumexp(z, axis=1) - jnp.take_along_axis(
        z, labels[:, None], axis=1)[:, 0]
    return jnp.sum(nll), jnp.sum(jnp.argmax(z, 1) == labels)


def evaluate(params, test: dict, dtype=jnp.float32, block: int = 1000):
    """(accuracy, mean loss) over the whole test set, in blocks."""
    n = len(test["label"])
    nll = hits = 0.0
    for s in range(0, n, block):
        a, b = _eval_block(params, test["image"][s:s + block],
                           test["label"][s:s + block], dtype=dtype)
        nll += float(a)
        hits += float(b)
    return hits / n, nll / n
