"""AMA (paper Eq. 5) unit tests + convex-combination properties, through
the fused server plane the round dispatches."""
import jax.numpy as jnp
import numpy as np
import pytest

hyp = pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import FLConfig
from repro.core import strategies
from repro.core.strategies.mix import alpha_schedule
from repro.kernels.ref import _norm_weights


def tiny_tree(rng, C=None):
    shape = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    if C is None:
        return {"a": shape(3, 4), "b": {"c": shape(5)}}
    return {"a": shape(C, 3, 4), "b": {"c": shape(C, 5)}}


def sched(sizes, delayed=None, limited=None):
    C = len(sizes)
    return {"data_sizes": jnp.asarray(sizes, jnp.float32),
            "delayed": jnp.asarray(np.zeros(C, bool) if delayed is None
                                   else delayed),
            "limited": jnp.asarray(np.zeros(C, bool) if limited is None
                                   else limited),
            "delays": jnp.ones((C,), jnp.int32)}


def server_update(fl, t, prev, clients, sch):
    out, _ = strategies.resolve(fl).fused_server_update(t, prev, clients,
                                                        sch, {})
    return out


def test_alpha_schedule_matches_paper():
    fl = FLConfig(alpha0=0.1, eta=2.5e-3)
    assert np.isclose(float(alpha_schedule(fl, 0)), 0.1)
    assert np.isclose(float(alpha_schedule(fl, 100)), 0.35)
    # capped
    assert float(alpha_schedule(fl, 10_000)) == pytest.approx(fl.alpha_cap)
    # the alpha the telemetry reports is the one the mix applies
    assert float(strategies.resolve(fl).mix_coefficient(100, None, None)) \
        == float(alpha_schedule(fl, 100))


def test_ama_aggregate_hand_computed():
    rng = np.random.RandomState(0)
    fl = FLConfig(algorithm="ama", alpha0=0.2, eta=0.0)
    prev = tiny_tree(rng)
    clients = tiny_tree(rng, C=3)
    out = server_update(fl, 0, prev, clients, sched([1.0, 2.0, 1.0]))
    w = np.array([0.25, 0.5, 0.25])
    for key in ("a",):
        want = 0.2 * np.asarray(prev[key]) + 0.8 * np.einsum(
            "c...,c->...", np.asarray(clients[key]), w)
        np.testing.assert_allclose(np.asarray(out[key]), want, rtol=1e-5)


def test_all_delayed_falls_back_to_prev():
    rng = np.random.RandomState(1)
    fl = FLConfig(algorithm="ama", alpha0=0.3, eta=0.0)
    prev = tiny_tree(rng)
    clients = tiny_tree(rng, C=2)
    out = server_update(fl, 0, prev, clients,
                        sched([1.0, 1.0], delayed=[True, True]))
    np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(prev["a"]),
                               rtol=1e-5)


def test_fedavg_drops_excluded_clients():
    """FedAvg keeps a client only if it is on time AND not limited."""
    rng = np.random.RandomState(2)
    prev = tiny_tree(rng)
    clients = tiny_tree(rng, C=4)
    out = server_update(FLConfig(algorithm="fedavg"), 0, prev, clients,
                        sched([1.0, 5.0, 3.0, 2.0],
                              delayed=[False, True, False, False],
                              limited=[False, False, False, True]))
    w = np.array([0.25, 0.0, 0.75, 0.0])
    want = np.einsum("c...,c->...", np.asarray(clients["a"]), w)
    np.testing.assert_allclose(np.asarray(out["a"]), want, rtol=1e-5)


@settings(deadline=None, max_examples=50)
@given(st.floats(0.01, 0.5), st.floats(0.0, 0.01), st.integers(0, 400))
def test_alpha_beta_convex(alpha0, eta, t):
    fl = FLConfig(alpha0=alpha0, eta=eta)
    a = float(alpha_schedule(fl, t))
    assert 0.0 < a <= fl.alpha_cap + 1e-6
    assert 0.0 <= 1.0 - a < 1.0


@settings(deadline=None, max_examples=30)
@given(st.lists(st.floats(0.5, 100.0), min_size=1, max_size=8))
def test_normalized_weights_sum_to_one(sizes):
    w, tot = _norm_weights(jnp.asarray(sizes), jnp.ones((len(sizes),)))
    assert np.isclose(float(jnp.sum(w)), 1.0, atol=1e-5)
