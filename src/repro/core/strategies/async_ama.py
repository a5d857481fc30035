"""Asynchronous AMA (paper Eqs. 6-11) as a ServerStrategy.

The O(max_delay) ring buffer of gamma^- pre-weighted pending updates is
strategy-owned aux state: it rides the round-loop carry (including
through the fused ``lax.scan`` engine) instead of living as a special
"queue" field the round loop has to know about.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import async_ama
from repro.core.strategies.ama import AMAStrategy
from repro.core.strategies.base import register


@register
class AsyncAMAStrategy(AMAStrategy):
    name = "async_ama"
    aliases = ()
    stateful = True

    def init_state(self, params):
        return {"queue": async_ama.init_queue(self.fl, params)}

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
        g = (async_ama.gamma_unnorm(fl, delays)
             * sched["delayed"].astype(jnp.float32))
        onehot = jax.nn.one_hot(arrival, Q, dtype=jnp.float32) * g[:, None]
        qgamma = aux_state["queue"]["gamma"] + jnp.sum(onehot, axis=0)
        stale_gamma = qgamma[jnp.asarray(t, jnp.int32) % Q]
        A = jnp.minimum(fl.alpha0 + fl.eta * jnp.asarray(t, jnp.float32),
                        fl.alpha_cap)
        return async_ama.ALPHA_UNNORM / (async_ama.ALPHA_UNNORM
                                         + stale_gamma) * A

    def aggregate(self, t, prev_global, client_params, sched, aux_state):
        on_time = jnp.logical_not(sched["delayed"])
        queue = async_ama.enqueue(self.fl, aux_state["queue"], t,
                                  client_params, sched["delayed"],
                                  sched["delays"])
        new_global, queue = async_ama.async_ama_aggregate(
            self.fl, t, prev_global, client_params, sched["data_sizes"],
            on_time, queue, use_kernel=self.fl.use_kernel)
        return new_global, {"queue": queue}

    def compressed_server_update(self, t, prev_global, groups, sched,
                                 aux_state):
        """The ring-buffer enqueue needs the DENSE delayed updates (they
        persist across rounds at full precision), so the AMA-family
        compressed hook this class inherits does not apply — revert to
        NotImplemented and let the round engine densify the payload
        before ``fused_server_update``."""
        del t, prev_global, groups, sched, aux_state
        return NotImplemented

    def fused_server_update(self, t, prev_global, client_params, sched,
                            aux_state):
        if self.server_impl == "legacy":
            return self.aggregate(t, prev_global, client_params, sched,
                                  aux_state)
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
