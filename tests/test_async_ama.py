"""Asynchronous AMA (paper Eqs. 6-11): weighting scheme + ring buffer.

The ring buffer of the fused server plane is validated against a NAIVE
event-list simulator that literally keeps every delayed update and
applies Eqs. 9-11 at arrival.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hyp = pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import FLConfig
from repro.core import strategies
from repro.core.strategies import async_ama as aa


def test_gamma_matches_paper_formula():
    fl = FLConfig(staleness_b=0.6)
    for s in [1, 2, 5, 15]:
        want = 0.6 * (1.0 - 1.0 / (1.0 + np.exp(-s)))
        assert np.isclose(float(aa.gamma_unnorm(fl, s)), want, rtol=1e-6)
    # monotone: staler updates weigh less
    gs = [float(aa.gamma_unnorm(fl, s)) for s in range(1, 16)]
    assert all(a > b for a, b in zip(gs, gs[1:]))


@settings(deadline=None, max_examples=50)
@given(st.floats(0.05, 0.4), st.floats(0.0, 5e-3), st.integers(0, 300),
       st.lists(st.integers(1, 15), min_size=0, max_size=6),
       st.floats(0.2, 1.0))
def test_mixing_weights_partition_of_unity(alpha0, eta, t, stalenesses, b):
    """Eq. 7: alpha + beta + sum(gamma) == 1; Eq. 8: alpha + sum(gamma) ==
    alpha0 + eta*t; all weights >= 0; alpha dominates every gamma."""
    fl = FLConfig(alpha0=alpha0, eta=eta, staleness_b=b)
    alpha, beta, gammas = aa.mixing_weights(fl, t, stalenesses)
    A = min(alpha0 + eta * t, fl.alpha_cap)
    assert np.isclose(alpha + beta + sum(gammas), 1.0, atol=1e-6)
    assert np.isclose(alpha + sum(gammas), A, atol=1e-6)
    assert alpha >= 0 and beta >= 0 and all(g >= 0 for g in gammas)
    # paper: alpha^- = 1 - sigmoid(1) >= gamma^- = b(1-sigmoid(s)) requires
    # b <= ~ (1-sig(1))/(1-sig(s)); with b<=1 and s>=1 it always holds
    for g in gammas:
        assert alpha >= g - 1e-9


def _params(rng):
    return {"w": jnp.asarray(rng.randn(4, 3), jnp.float32)}


def test_ring_buffer_vs_event_list():
    """Drive 12 rounds with random delays through (a) the ring buffer and
    (b) a literal event-list simulation; the aggregated models must match."""
    rng = np.random.RandomState(0)
    fl = FLConfig(alpha0=0.1, eta=2.5e-3, staleness_b=0.6, max_delay=4,
                  clients_per_round=3)
    C = fl.clients_per_round
    strat = strategies.resolve(fl)
    prev_rb = _params(rng)
    prev_ev = jax.tree.map(jnp.copy, prev_rb)
    aux = strat.init_state(prev_rb)
    pending_events = []   # (arrival_t, sent_t, params)

    for t in range(12):
        client_params = {"w": jnp.asarray(rng.randn(C, 4, 3), jnp.float32)}
        sizes = jnp.asarray(rng.rand(C) + 0.5, jnp.float32)
        delayed = rng.rand(C) < 0.5
        delays = np.where(delayed, rng.randint(1, fl.max_delay + 1, C), 1)

        # --- ring buffer path
        sched = {"data_sizes": sizes, "delayed": jnp.asarray(delayed),
                 "delays": jnp.asarray(delays, jnp.int32),
                 "limited": jnp.zeros((C,), bool)}
        prev_rb, aux = strat.fused_server_update(t, prev_rb, client_params,
                                                 sched, aux)

        # --- event list path
        for i in range(C):
            if delayed[i]:
                pending_events.append(
                    (t + int(delays[i]), t,
                     jax.tree.map(lambda x, i=i: x[i], client_params)))
        arrivals = [(n, p) for (at, n, p) in pending_events if at == t]
        pending_events = [(at, n, p) for (at, n, p) in pending_events
                          if at != t]
        stalenesses = [t - n for (n, _) in arrivals]
        alpha, beta, gammas = aa.mixing_weights(fl, t, stalenesses)
        w = np.asarray(sizes) * (~delayed)
        if w.sum() > 0:
            w = w / w.sum()
            agg = np.einsum("cij,c->ij", np.asarray(client_params["w"]), w)
        else:
            agg = np.asarray(prev_ev["w"])
        new = alpha * np.asarray(prev_ev["w"]) + beta * agg
        for g, (_, p) in zip(gammas, arrivals):
            new = new + g * np.asarray(p["w"])
        prev_ev = {"w": jnp.asarray(new)}

        np.testing.assert_allclose(np.asarray(prev_rb["w"]), new,
                                   rtol=2e-5, atol=2e-6,
                                   err_msg=f"round {t}")


def test_sync_limit_no_delays_equals_plain_ama():
    """With no delayed updates the async path must reduce to Eq. 5."""
    rng = np.random.RandomState(1)
    fl = FLConfig(alpha0=0.15, eta=1e-3, max_delay=5)
    prev = _params(rng)
    C = 4
    cp = {"w": jnp.asarray(rng.randn(C, 4, 3), jnp.float32)}
    sched = {"data_sizes": jnp.ones((C,), jnp.float32),
             "delayed": jnp.zeros((C,), bool),
             "delays": jnp.ones((C,), jnp.int32),
             "limited": jnp.zeros((C,), bool)}
    strat = strategies.resolve(fl)
    assert isinstance(strat, strategies.AsyncAMAStrategy)
    got, _ = strat.fused_server_update(3, prev, cp, sched,
                                       strat.init_state(prev))
    want, _ = strategies.resolve(fl.with_(max_delay=0)).fused_server_update(
        3, prev, cp, sched, {})
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want["w"]),
                               rtol=1e-5)
