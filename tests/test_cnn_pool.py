"""The paper CNN's 2x2 / stride-2 max-pool against ``lax.reduce_window``.

``models/cnn.py`` pools with its own helper, whose gradient gives each
window's output gradient to the first maximum in row-major window order,
as the ``select_and_scatter`` gradient of ``reduce_window`` does. Both
the forward and the gradient must equal the ``reduce_window`` form bit
for bit, ties included: pooling follows a ReLU, so whole windows of
equal values (zeros, and plateaus of the input) are common.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.models import cnn
from repro.models.layers import cross_entropy_loss, dense

CLIENTS = 3
#: the paper CNN's two pool inputs at a small batch: conv1's (24x24x10)
#: and conv2's (8x8x20) outputs
SHAPES = [(4, 24, 24, 10), (4, 8, 8, 20)]


def _pool_reduce_window(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _pool_even_split(x):
    """The plain reshape + max: its autodiff splits a tie equally."""
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def _input(kind, shape, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(CLIENTS, *shape)).astype(np.float32)
    if kind == "ties":
        # halves, so neighbours often tie; plus whole equal windows,
        # positive and zero, on both sides of the ReLU
        x = np.round(2 * x) / 2
        x[:, :, 0:4, 0:4] = 0.5
        x[:, :, 4:6, 2:6] = 0.0
        x[:, :, 6:8, 0:2] = -1.0
    return jnp.asarray(x)


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _pool_and_grad(pool, x, ct):
    """Forward and input gradient of pool(relu(x)), vmapped over clients."""
    def one(xi, ci):
        y, vjp = jax.vjp(lambda v: pool(jax.nn.relu(v)), xi)
        return y, vjp(ci)[0]
    return jax.jit(jax.vmap(one))(x, ct)


@pytest.mark.parametrize("kind", ["normal", "ties"])
@pytest.mark.parametrize("shape", SHAPES, ids=["pool1", "pool2"])
def test_pool_equals_reduce_window_bit_for_bit(shape, kind):
    x = _input(kind, shape)
    b, h, w, c = shape
    ct = jax.random.normal(jax.random.PRNGKey(1),
                           (CLIENTS, b, h // 2, w // 2, c))
    y, dx = _pool_and_grad(cnn.max_pool_2x2, x, ct)
    y_ref, dx_ref = _pool_and_grad(_pool_reduce_window, x, ct)
    np.testing.assert_array_equal(_bits(y), _bits(y_ref))
    np.testing.assert_array_equal(_bits(dx), _bits(dx_ref))


@pytest.mark.parametrize("shape", SHAPES, ids=["pool1", "pool2"])
def test_tie_input_tells_first_max_from_even_split(shape):
    """The tie input can see the difference the helper exists for."""
    x = _input("ties", shape)
    b, h, w, c = shape
    ct = jnp.ones((CLIENTS, b, h // 2, w // 2, c), jnp.float32)
    _, dx_ref = _pool_and_grad(_pool_reduce_window, x, ct)
    _, dx_split = _pool_and_grad(_pool_even_split, x, ct)
    assert not np.array_equal(np.asarray(dx_split), np.asarray(dx_ref))


def test_pool_rejects_odd_sizes():
    with pytest.raises(ValueError, match="even"):
        cnn.max_pool_2x2(jnp.zeros((1, 5, 4, 2)))


def _forward_reduce_window(params, cfg, batch):
    """``cnn.forward`` as it pooled with ``reduce_window``, with the
    same convolutions, so that only the pooling differs."""
    x = batch["image"].astype(jnp.float32)
    x = jax.nn.relu(cnn.conv_valid(x, params["body"]["conv1"]["w"]))
    x = _pool_reduce_window(x)
    x = jax.nn.relu(cnn._conv(x, params["body"]["conv2"]["w"]))
    x = _pool_reduce_window(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dense(params["fc1"], x))
    x = jax.nn.relu(dense(params["fc2"], x))
    return dense(params["fc3"], x)


def _images(seed):
    """Half noise, half 4x4 plateaus: the convolutions then give whole
    windows of equal positive values."""
    rng = np.random.default_rng(seed)
    noise = rng.normal(size=(CLIENTS, 4, 28, 28, 1))
    flat = np.kron(rng.normal(size=(CLIENTS, 4, 7, 7, 1)),
                   np.ones((1, 1, 4, 4, 1)))
    return jnp.asarray(np.concatenate([noise, flat], 1), jnp.float32)


@pytest.mark.parametrize("seed", [0, 1])
def test_cnn_loss_grad_equals_reduce_window_forward(seed):
    cfg = get_arch("paper-cnn")
    keys = jax.random.split(jax.random.PRNGKey(seed), CLIENTS)
    params = jax.vmap(lambda k: cnn.init_params(cfg, k))(keys)
    batch = {"image": _images(seed),
             "label": jnp.asarray(np.random.default_rng(seed).integers(
                 0, cfg.vocab_size, (CLIENTS, 8)), jnp.int32)}

    def loss_ref(p, b):
        return cross_entropy_loss(_forward_reduce_window(p, cfg, b),
                                  b["label"])

    grad = jax.jit(jax.vmap(jax.grad(lambda p, b: cnn.loss_fn(p, cfg, b))))
    grad_ref = jax.jit(jax.vmap(jax.grad(loss_ref)))
    g, g_ref = grad(params, batch), grad_ref(params, batch)
    for a, r in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_array_equal(_bits(a), _bits(r))
