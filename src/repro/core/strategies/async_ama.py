"""Asynchronous AMA (paper §IV-B, Eqs. 6-11) as a ServerStrategy.

Delayed updates from round n arriving at round t enter the aggregation with
a staleness weight

    gamma_i^- = b * (1 - sigmoid(t - n))          (Eq. 9)
    alpha^-   = 1 - sigmoid(1)

normalised so the "old knowledge" budget alpha + sum(gamma_i) equals the AMA
schedule alpha0 + eta*t (Eq. 8) and alpha + beta + sum(gamma) = 1 (Eq. 7):

    alpha   = alpha^- / (alpha^- + sum_i gamma_i^-) * (alpha0 + eta t)
    gamma_i = gamma_i^- / (alpha^- + sum_i gamma_i^-) * (alpha0 + eta t)

Server-side state is a RING BUFFER over arrival rounds: an update sent at
round n with delay d arrives at n+d; its staleness d is known at send time,
so the server accumulates gamma^-(d) * omega into slot (n+d) % Q together
with the scalar sum of gamma^-. At round t the slot t % Q holds exactly
sum_i gamma_i^- omega_ni and sum_i gamma_i^- — O(max_delay) parameter
buffers regardless of client count, which is what makes the paper's scheme
feasible when omega is billions of parameters. The buffer is
strategy-owned aux state: it rides the round-loop carry (including
through the fused ``lax.scan`` engine).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core.strategies.ama import AMAStrategy
from repro.core.strategies.base import register
from repro.core.strategies.mix import alpha_schedule

#: alpha^- = 1 - sigmoid(1) (paper Eq. 9), computed as sigmoid(-1): the
#: same float32 expression ``gamma_unnorm`` gives at staleness 1, b = 1
ALPHA_UNNORM = jax.nn.sigmoid(-1.0)


def gamma_unnorm(fl: FLConfig, staleness):
    """gamma_i^- = b * (1 - sigmoid(staleness)); staleness = t - n >= 1.

    Computed as b * sigmoid(-s): algebraically identical, but avoids the
    catastrophic cancellation of 1 - sigmoid(s) for stale updates (f32
    1-sigmoid(15) loses all significant digits)."""
    s = jnp.asarray(staleness, jnp.float32)
    return fl.staleness_b * jax.nn.sigmoid(-s)


def init_queue(fl: FLConfig, params_like):
    """Ring buffer of gamma^- pre-weighted pending sums.

    Q = max_delay + 1 slots so an update with the maximum delay, enqueued
    at round t, never collides with the slot being drained at round t.
    """
    Q = max(fl.max_delay, 1) + 1
    zeros = jax.tree.map(
        lambda x: jnp.zeros((Q,) + x.shape, jnp.float32), params_like)
    return {"sum": zeros, "gamma": jnp.zeros((Q,), jnp.float32)}


def mixing_weights(fl: FLConfig, t, staleness_list):
    """Reference computation of (alpha, beta, gammas) for a set of stale
    updates — used by tests/benchmarks to check Eqs. 7-11 analytically."""
    A = float(alpha_schedule(fl, t))
    g_un = [float(gamma_unnorm(fl, s)) for s in staleness_list]
    denom = float(ALPHA_UNNORM) + sum(g_un)
    alpha = float(ALPHA_UNNORM) / denom * A
    gammas = [g / denom * A for g in g_un]
    beta = 1.0 - A
    return alpha, beta, gammas


@register
class AsyncAMAStrategy(AMAStrategy):
    name = "async_ama"
    aliases = ()
    stateful = True
    # the ring-buffer enqueue needs the DENSE delayed updates (they
    # persist across rounds at full precision): the round densifies a
    # compressed payload before ``fused_server_update``
    consumes_compressed = False

    def init_state(self, params):
        return {"queue": init_queue(self.fl, params)}

    def mix_coefficient(self, t, sched, aux_state):
        """The REALIZED Eq. 10 alpha of this round: the Eq. 8 budget
        A = alpha0 + eta*t renormalized by the staleness mass actually
        arriving now — the popped slot's gamma^- after this round's
        enqueue (the same order the update applies them). A pure
        scalar replay of the ring-buffer bookkeeping; the buffer
        itself is untouched."""
        fl = self.fl
        Q = aux_state["queue"]["gamma"].shape[0]
        delays = sched["delays"]
        arrival = (jnp.asarray(t, jnp.int32) + delays) % Q
        g = gamma_unnorm(fl, delays) * sched["delayed"].astype(jnp.float32)
        onehot = jax.nn.one_hot(arrival, Q, dtype=jnp.float32) * g[:, None]
        qgamma = aux_state["queue"]["gamma"] + jnp.sum(onehot, axis=0)
        stale_gamma = qgamma[jnp.asarray(t, jnp.int32) % Q]
        A = alpha_schedule(fl, t)
        return ALPHA_UNNORM / (ALPHA_UNNORM + stale_gamma) * A

    def fused_server_update(self, t, prev_global, client_params, sched,
                            aux_state):
        from repro.kernels.server_plane import server_async_tree
        new_global, queue = server_async_tree(
            prev_global, client_params, aux_state["queue"],
            sched["data_sizes"], sched["delayed"].astype(jnp.float32),
            sched["delays"], t, self._hyp(), impl=self.server_impl)
        return new_global, {"queue": queue}

    def _hyp(self):
        """(4,) f32 = [alpha0, eta, alpha_cap, staleness_b]."""
        fl = self.fl
        return jnp.asarray([fl.alpha0, fl.eta, fl.alpha_cap,
                            fl.staleness_b], jnp.float32)

    def reduced_server_update(self, t, prev_global, client_params, sched,
                              aux_state):
        """``kernels.ref.server_async_math`` with the client axis
        pre-reduced: the on-time aggregate AND the Q ring-buffer enqueue
        sums are ONE (C, 1+Q) ``reduce_leading`` contraction, so the
        per-round collective moves (1+Q) x N bytes instead of C x N."""
        from repro.kernels.ref import async_layout, server_async_coefs
        from repro.sharding.ctx import reduce_leading
        queue = aux_state["queue"]
        C, Q = sched["delays"].shape[0], queue["gamma"].shape[0]
        tt = jnp.asarray(t, jnp.int32)
        c, new_qgamma = server_async_coefs(
            queue["gamma"], sched["data_sizes"],
            sched["delayed"].astype(jnp.float32), sched["delays"],
            jnp.stack([tt, tt % Q]), self._hyp())
        o = async_layout(C, Q)
        sel = c[o["sel"]:o["sel"] + Q]                          # pop mask

        # col 0: beta-weighted on-time aggregate; cols 1..Q: enqueue
        W = jnp.concatenate([c[o["w"]:o["w"] + C, None],
                             c[o["onehot"]:o["sel"]].reshape(C, Q)], axis=1)
        red = reduce_leading(client_params, W)        # leaves (1+Q, ...)
        rows = jax.tree.map(lambda qs, r: qs + r[1:], queue["sum"], red)

        def selb(x):
            return sel.reshape((Q,) + (1,) * (x.ndim - 1))

        new_params = jax.tree.map(
            lambda p, r, rw: (p.astype(jnp.float32) * c[0] + r[0]
                              + jnp.sum(rw * selb(rw), axis=0)
                              * c[o["gscale"]]).astype(p.dtype),
            prev_global, red, rows)
        new_qsum = jax.tree.map(lambda rw: rw * (1.0 - selb(rw)), rows)
        return new_params, {"queue": {"sum": new_qsum,
                                      "gamma": new_qgamma}}
