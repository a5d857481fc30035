"""The mix family: one server update for ama, fedavg and fedprox.

Each of these rules mixes the previous global model with the weighted
average of the clients it keeps (paper Eq. 5):

    omega_t = alpha_t * omega_{t-1} + beta_t * sum_i w_i * omega_ti
    alpha_t = min(alpha0 + eta * t, alpha_cap)      beta_t = 1 - alpha_t

with w_i = |d_i| / sum_{j kept} |d_j| over the KEPT clients (the FedAvg
convention: the paper's |d_i|/|D| over the full federated dataset would
shrink the model by alpha + beta * (m/K) each round), and the whole beta
budget reverted to the previous model when nobody is kept. The rules
differ in two facts only:

  * ``keep(sched)`` — which clients' updates the round mixes;
  * ``adaptive`` — whether alpha follows Eq. 5 (AMA) or is 0 (a plain
    weighted average: fedavg, fedprox).

``MixStrategy`` states the fused, compressed and pre-reduced updates
once over those two facts.
"""
from __future__ import annotations

import abc

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core.strategies.base import ServerStrategy
from repro.kernels.ref import server_mix_coefs
from repro.kernels.server_plane import (mix_coefs,
                                        server_mix_compressed_tree,
                                        server_mix_tree)
from repro.sharding.ctx import reduce_leading


def alpha_schedule(fl: FLConfig, t):
    """Eq. 5: alpha_t = alpha0 + eta*t, capped to keep beta > 0 on long
    runs."""
    return jnp.minimum(fl.alpha0 + fl.eta * jnp.asarray(t, jnp.float32),
                       fl.alpha_cap)


def on_time(sched):
    """The clients whose update arrives this round."""
    return jnp.logical_not(sched["delayed"])


class MixStrategy(ServerStrategy):
    """A rule of the mix family: subclasses give ``keep`` and
    ``adaptive``, and their client-side hooks."""

    consumes_compressed = True
    #: whether alpha follows Eq. 5; False zeroes it
    adaptive: bool

    @abc.abstractmethod
    def keep(self, sched):
        """(C,) bool: the clients whose update this round mixes."""

    def _keep(self, sched):
        return self.keep(sched).astype(jnp.float32)

    def _coefs(self, t):
        return mix_coefs(self.fl, t, adaptive=self.adaptive)

    def mix_coefficient(self, t, sched, aux_state):
        """The alpha the mix applies this round: Eq. 5, or 0."""
        del sched, aux_state
        if self.adaptive:
            return alpha_schedule(self.fl, t)
        return jnp.float32(0.0)

    def fused_server_update(self, t, prev_global, client_params, sched,
                            aux_state):
        keep = self._keep(sched)
        new_global = server_mix_tree(
            prev_global, client_params, sched["data_sizes"], keep,
            self._coefs(t), impl=self.server_impl)
        return new_global, aux_state

    def compressed_server_update(self, t, prev_global, groups, sched,
                                 aux_state):
        """The mix consuming compressed deltas in-kernel (q8/bf16 rows,
        or top-k pairs densified first): ``groups`` is ``repro.comm``'s
        flat per-dtype-group payload list (``[(leaf_idxs, payload)]``,
        see ``kernels.server_plane.server_mix_compressed_tree``)."""
        keep = self._keep(sched)
        new_global = server_mix_compressed_tree(
            prev_global, groups, sched["data_sizes"], keep,
            self._coefs(t), impl=self.server_impl)
        return new_global, aux_state

    def reduced_server_update(self, t, prev_global, client_params, sched,
                              aux_state):
        """``kernels.ref.server_mix_math`` with the client axis
        pre-reduced: out = a_eff*prev + sum_k (beta*w_k)*x_k, where the
        weighted sum is ONE ``reduce_leading`` contraction (an N-byte
        collective on a sharded mesh)."""
        keep = self._keep(sched)
        c = server_mix_coefs(sched["data_sizes"], keep,
                             self._coefs(t))            # [a_eff, beta*w]
        red = reduce_leading(client_params, c[1:])
        new_global = jax.tree.map(
            lambda p, r: (p.astype(jnp.float32) * c[0] + r).astype(p.dtype),
            prev_global, red)
        return new_global, aux_state
