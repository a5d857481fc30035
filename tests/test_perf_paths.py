"""Regression tests for the §Perf-adopted code paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig, reduced
from repro.configs.registry import ARCHS
from repro.core.round import init_state, make_round_step
from repro.kernels import ref
from repro.models import moe
from repro.models.api import build_model
from repro.models.attention import chunked_attention


def test_grouped_moe_matches_global_dispatch():
    """Blocked dispatch (H1-it1) == global dispatch at ample capacity."""
    cfg = reduced(ARCHS["mixtral-8x22b"]).with_(
        dtype="float32", capacity_factor=8.0)
    p = moe.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 64, cfg.d_model),
                    jnp.float32)
    o1, _ = moe.moe_apply(p, cfg, x)
    o2, _ = moe.moe_apply(p, cfg.with_(moe_group_size=32), x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [0, 48])
def test_blocked_chunked_attention_matches_ref(window):
    """H1-it3: q-block x kv-chunk skipping must not change the math."""
    rng = np.random.RandomState(0)
    B, S, H, hd = 2, 128, 2, 32
    q = jnp.asarray(rng.randn(B, S, H, hd), jnp.float32) * 0.4
    k = jnp.asarray(rng.randn(B, S, H, hd), jnp.float32) * 0.4
    v = jnp.asarray(rng.randn(B, S, H, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    got = chunked_attention(q, k, v, pos, pos, causal=True, window=window,
                            chunk=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_chunked_attention_unaligned_cross():
    """Non-self-attention path (whisper cross-attn): no skipping, exact."""
    rng = np.random.RandomState(1)
    B, Sq, Skv, H, hd = 1, 48, 80, 2, 16
    q = jnp.asarray(rng.randn(B, Sq, H, hd), jnp.float32) * 0.4
    k = jnp.asarray(rng.randn(B, Skv, H, hd), jnp.float32) * 0.4
    v = jnp.asarray(rng.randn(B, Skv, H, hd), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(Sq), (B, Sq))
    kpos = jnp.broadcast_to(jnp.arange(Skv), (B, Skv))
    got = chunked_attention(q, k, v, qpos, kpos, causal=False, chunk=32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _fes_static_round(cfg):
    """One fes_static round of ``cfg``: the params before, the state and
    metrics after, and the model and batch it ran on."""
    model = build_model(cfg)
    fl = FLConfig(algorithm="ama_fes", fes_static=True, lr=0.05)
    state = init_state(model, fl, jax.random.PRNGKey(0))
    step = jax.jit(make_round_step(model, fl))
    batch = {"tokens": jnp.ones((2, 1, 2, 16), jnp.int32)}
    sched = {"limited": jnp.ones((2,), bool),
             "delayed": jnp.zeros((2,), bool),
             "delays": jnp.ones((2,), jnp.int32),
             "data_sizes": jnp.ones((2,), jnp.float32)}
    p0 = jax.tree.map(jnp.copy, state["params"])
    state, metrics = step(state, batch, sched)
    assert np.isfinite(float(metrics["loss"]))
    assert not np.array_equal(
        np.asarray(p0["lm_head"]["w"], np.float32),
        np.asarray(state["params"]["lm_head"]["w"], np.float32))
    return p0, state, model, fl, batch


def test_fes_static_round_runs_and_freezes_body():
    """H3-it1: the fes_static round trains only the classifier."""
    # masters in the compute dtype, as this test has always pinned
    p0, state, *_ = _fes_static_round(
        reduced(ARCHS["minitron-8b"]).with_(param_dtype=""))
    # body frozen up to the AMA mix with the (identical) prev body:
    np.testing.assert_array_equal(
        np.asarray(p0["embed"]["table"], np.float32),
        np.asarray(state["params"]["embed"]["table"], np.float32))


def test_fes_static_round_f32_masters_body_within_ulp():
    """With f32 masters under bf16 compute the client plane leaves every
    cohort's body exactly as it was, and the AMA mix of that body with
    the (identical) prev body gives it back up to the rounding of Eq. 5
    in f32: one ulp."""
    from repro.core.client import make_fes_local_train
    p0, state, model, fl, batch = _fes_static_round(
        reduced(ARCHS["minitron-8b"]))
    assert p0["embed"]["table"].dtype == jnp.float32
    trained, _ = jax.jit(make_fes_local_train(model, fl))(p0, batch)
    for c in range(2):
        np.testing.assert_array_equal(
            np.asarray(p0["embed"]["table"]),
            np.asarray(trained["embed"]["table"][c]))
    body0 = np.asarray(p0["embed"]["table"])
    body1 = np.asarray(state["params"]["embed"]["table"])
    assert np.all(np.abs(body1 - body0) <= np.spacing(np.abs(body0)))


def test_constrain_noop_without_mesh():
    from repro.sharding.ctx import constrain
    x = jnp.ones((4, 6))
    y = constrain(x, None, "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
