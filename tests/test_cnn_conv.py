"""The paper CNN's convolution helper against ``lax.conv_general_dilated``.

``models/cnn.py`` ``conv_valid`` runs lax's convolution forward and
takes the filter's gradient as a dot of the input's row slices with the
output's gradient (``_filter_grad``). The forward must equal lax's bit
for bit; the gradients, which sum in another order, must match lax's
autodiff to f32 rounding: the largest gap at most ``RTOL`` of the
largest magnitude.
The checks run under ``vmap`` over clients, inside ``jit`` and a
``lax.scan`` step, as the client plane runs the model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.models import cnn
from repro.models.layers import cross_entropy_loss, dense

CLIENTS = 3
RTOL = 1e-5
#: (input, filter) of the paper CNN's two convolutions at a small batch
SHAPES = [((4, 28, 28, 1), (5, 5, 1, 10)), ((4, 12, 12, 10), (5, 5, 10, 20))]
IDS = ["conv1", "conv2"]


def _input(kind, shape, seed=0):
    """Noise, or noise with constant regions (blank margins and flat
    strokes, as images have), so many patches are equal."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(CLIENTS, *shape)).astype(np.float32)
    if kind == "plateaus":
        x[:, :, :6, :, :] = 0.0
        x[:, :, 8:14, 3:12, :] = 1.5
        x[:, 1:3] = np.round(x[:, 1:3])
    return jnp.asarray(x)


def _filters(shape, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(0.1 * rng.normal(size=(CLIENTS, *shape)), jnp.float32)


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _assert_close(a, r):
    a, r = np.asarray(a), np.asarray(r)
    assert a.shape == r.shape
    gap = np.max(np.abs(a - r))
    assert gap <= RTOL * np.max(np.abs(r)), (gap, np.max(np.abs(r)))


@pytest.mark.parametrize("xs,ws", SHAPES, ids=IDS)
def test_forward_equals_lax_bit_for_bit(xs, ws):
    x, w = _input("normal", xs), _filters(ws)
    y = jax.jit(jax.vmap(cnn.conv_valid))(x, w)
    y_ref = jax.jit(jax.vmap(cnn._conv))(x, w)
    np.testing.assert_array_equal(_bits(y), _bits(y_ref))


def _scan_filter_grads(conv, x, w, steps=2, lr=0.05):
    """Each client's filter gradients over ``steps`` SGD steps of a
    nonlinear loss, in a vmapped ``lax.scan`` under ``jit``."""
    def one(xi, wi):
        shape = jax.eval_shape(conv, xi, wi).shape
        ct = jnp.cos(jnp.arange(np.prod(shape), dtype=jnp.float32)
                     ).reshape(shape)

        def loss(v):
            return jnp.sum(jnp.tanh(conv(xi, v)) * ct)

        def step(v, _):
            g = jax.grad(loss)(v)
            return v - lr * g, g
        return jax.lax.scan(step, wi, None, length=steps)[1]
    return jax.jit(jax.vmap(one))(x, w)


@pytest.mark.parametrize("kind", ["normal", "plateaus"])
@pytest.mark.parametrize("xs,ws", SHAPES, ids=IDS)
def test_filter_grad_matches_lax_autodiff(xs, ws, kind):
    x, w = _input(kind, xs), _filters(ws)
    g = _scan_filter_grads(cnn.conv_valid, x, w)
    g_ref = _scan_filter_grads(cnn._conv, x, w)
    assert g.shape == (CLIENTS, 2, *ws)
    _assert_close(g, g_ref)


@pytest.mark.parametrize("xs,ws", SHAPES, ids=IDS)
def test_input_grad_matches_lax(xs, ws):
    x, w = _input("normal", xs), _filters(ws)

    def grads(conv):
        def one(xi, wi):
            y, vjp = jax.vjp(conv, xi, wi)
            return vjp(jnp.sin(y))
        return jax.jit(jax.vmap(one))(x, w)

    (dx, dw), (dx_ref, dw_ref) = grads(cnn.conv_valid), grads(cnn._conv)
    np.testing.assert_array_equal(_bits(dx), _bits(dx_ref))
    _assert_close(dw, dw_ref)


def _forward_lax(params, cfg, batch):
    """``cnn.forward`` as it was with both convolutions differentiated
    by lax's autodiff."""
    x = batch["image"].astype(jnp.float32)
    x = jax.nn.relu(cnn._conv(x, params["body"]["conv1"]["w"]))
    x = cnn.max_pool_2x2(x)
    x = jax.nn.relu(cnn._conv(x, params["body"]["conv2"]["w"]))
    x = cnn.max_pool_2x2(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dense(params["fc1"], x))
    x = jax.nn.relu(dense(params["fc2"], x))
    return dense(params["fc3"], x)


@pytest.mark.parametrize("kind", ["normal", "plateaus"])
def test_cnn_client_step_matches_lax_formulation(kind):
    cfg = get_arch("paper-cnn")
    keys = jax.random.split(jax.random.PRNGKey(3), CLIENTS)
    params = jax.vmap(lambda k: cnn.init_params(cfg, k))(keys)
    batch = {"image": _input(kind, (8, 28, 28, 1), seed=5),
             "label": jnp.asarray(np.random.default_rng(5).integers(
                 0, cfg.vocab_size, (CLIENTS, 8)), jnp.int32)}

    def loss_ref(p, b):
        return cross_entropy_loss(_forward_lax(p, cfg, b), b["label"])

    step = jax.jit(jax.vmap(jax.value_and_grad(
        lambda p, b: cnn.loss_fn(p, cfg, b))))
    step_ref = jax.jit(jax.vmap(jax.value_and_grad(loss_ref)))
    (loss, g), (loss_r, g_r) = step(params, batch), step_ref(params, batch)
    # the forward is the same op: the loss is the same bit for bit
    np.testing.assert_array_equal(_bits(loss), _bits(loss_r))
    for path, a in jax.tree_util.tree_leaves_with_path(g):
        r = g_r
        for k in path:
            r = r[k.key]
        _assert_close(a, r)


def test_round_filter_grad_is_a_dot_not_a_convolution():
    """conv1's filter gradient under the clients' vmap lowers to a
    batched dot; lax's autodiff lowers it to a grouped convolution."""
    x, w = _input("normal", SHAPES[0][0]), _filters(SHAPES[0][1])

    def text(conv):
        f = jax.vmap(jax.grad(lambda xi, wi: jnp.sum(conv(xi, wi) ** 2),
                              argnums=1))
        return jax.jit(f).lower(x, w).as_text()

    ours, ref = text(cnn.conv_valid), text(cnn._conv)
    assert ref.count("stablehlo.convolution") == 2
    assert ours.count("stablehlo.convolution") == 1
    assert "stablehlo.dot_general" in ours
