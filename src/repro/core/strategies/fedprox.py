"""FedProx baseline (paper Eq. 4): proximal gradient pull toward the
global model plus "partial work" — computing-limited devices run a
fraction of the local steps instead of masking gradients."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ama import fedavg_aggregate
from repro.core.strategies.base import (ServerStrategy,
                                        reduced_mix_update, register)


@register
class FedProxStrategy(ServerStrategy):
    name = "fedprox"

    def local_grad_transform(self, grads, params, global_params, fes_mask,
                             limited):
        del fes_mask, limited
        rho = self.fl.fedprox_rho
        return jax.tree.map(
            lambda gi, p, p0: gi + 2.0 * rho
            * (p.astype(jnp.float32)
               - p0.astype(jnp.float32)).astype(gi.dtype),
            grads, params, global_params)

    def local_steps(self, n_steps: int, limited):
        n_partial = self.static_local_steps(n_steps)
        return jnp.where(limited, jnp.int32(n_partial), jnp.int32(n_steps))

    def static_local_steps(self, n_steps: int) -> int:
        """Partial work: under the partitioned client plane a limited
        cohort's program scans only this many steps — the masked plane
        computes the full scan and discards the gradients instead."""
        return max(1, int(self.fl.fedprox_partial * n_steps))

    def aggregate(self, t, prev_global, client_params, sched, aux_state):
        del t
        on_time = jnp.logical_not(sched["delayed"])
        new_global = fedavg_aggregate(prev_global, client_params,
                                      sched["data_sizes"], on_time,
                                      use_kernel=self.fl.use_kernel)
        return new_global, aux_state

    def fused_server_update(self, t, prev_global, client_params, sched,
                            aux_state):
        if self.server_impl == "legacy":
            return self.aggregate(t, prev_global, client_params, sched,
                                  aux_state)
        from repro.kernels.server_plane import mix_coefs, server_mix_tree
        keep = jnp.logical_not(sched["delayed"]).astype(jnp.float32)
        new_global = server_mix_tree(
            prev_global, client_params, sched["data_sizes"], keep,
            mix_coefs(self.fl, t, adaptive=False), impl=self.server_impl)
        return new_global, aux_state

    def compressed_server_update(self, t, prev_global, groups, sched,
                                 aux_state):
        """On-time weighted average (alpha=0) over compressed deltas."""
        if self.server_impl == "legacy":
            return NotImplemented
        from repro.kernels.server_plane import (mix_coefs,
                                                server_mix_compressed_tree)
        keep = jnp.logical_not(sched["delayed"]).astype(jnp.float32)
        new_global = server_mix_compressed_tree(
            prev_global, groups, sched["data_sizes"], keep,
            mix_coefs(self.fl, t, adaptive=False), impl=self.server_impl)
        return new_global, aux_state

    def reduced_server_update(self, t, prev_global, client_params, sched,
                              aux_state):
        from repro.kernels.server_plane import mix_coefs
        keep = jnp.logical_not(sched["delayed"]).astype(jnp.float32)
        return reduced_mix_update(
            prev_global, client_params, sched["data_sizes"], keep,
            mix_coefs(self.fl, t, adaptive=False)), aux_state
