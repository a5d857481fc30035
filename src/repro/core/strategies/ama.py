"""Synchronous AMA (paper Eq. 5) as a ServerStrategy.

Client side this is the paper's AMA-FES pairing: when FES is enabled the
gradient of computing-limited devices is masked to the classifier split
(Eq. 2) via ``masked_update``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.ama import ama_aggregate
from repro.core.strategies.base import (ServerStrategy, reduced_mix_update,
                                        register)
from repro.optim.masked import masked_update


@register
class AMAStrategy(ServerStrategy):
    name = "ama"
    aliases = ("ama_fes",)   # seed config name; resolve() picks async when
                             # the environment has delays (max_delay > 0)

    def local_grad_transform(self, grads, params, global_params, fes_mask,
                             limited):
        del params, global_params
        if self.fl.fes_enabled:
            return masked_update(grads, fes_mask, limited)
        return grads

    @property
    def limited_mode(self) -> str:
        """Partitioned plane: limited cohorts differentiate only the
        classifier (Eq. 3) when FES is on — the executed counterpart of
        the masked plane's zeroed body gradients."""
        return "classifier" if self.fl.fes_enabled else "full"

    def mix_coefficient(self, t, sched, aux_state):
        """Eq. 5: alpha_t = min(alpha0 + eta*t, cap) — the adaptive
        schedule the fused mix applies this round."""
        del sched, aux_state
        fl = self.fl
        return jnp.minimum(fl.alpha0 + fl.eta
                           * jnp.asarray(t, jnp.float32), fl.alpha_cap)

    def aggregate(self, t, prev_global, client_params, sched, aux_state):
        on_time = jnp.logical_not(sched["delayed"])
        new_global = ama_aggregate(
            self.fl, t, prev_global, client_params, sched["data_sizes"],
            on_time, use_kernel=self.fl.use_kernel)
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
            mix_coefs(self.fl, t), impl=self.server_impl)
        return new_global, aux_state

    def compressed_server_update(self, t, prev_global, groups, sched,
                                 aux_state):
        """Eq. 5 mix consuming compressed deltas in-kernel (q8/bf16 rows
        or top-k scatter); "legacy" has no compressed path — the engine
        densifies and falls back."""
        if self.server_impl == "legacy":
            return NotImplemented
        from repro.kernels.server_plane import (mix_coefs,
                                                server_mix_compressed_tree)
        keep = jnp.logical_not(sched["delayed"]).astype(jnp.float32)
        new_global = server_mix_compressed_tree(
            prev_global, groups, sched["data_sizes"], keep,
            mix_coefs(self.fl, t), impl=self.server_impl)
        return new_global, aux_state

    def reduced_server_update(self, t, prev_global, client_params, sched,
                              aux_state):
        from repro.kernels.server_plane import mix_coefs
        keep = jnp.logical_not(sched["delayed"]).astype(jnp.float32)
        return reduced_mix_update(
            prev_global, client_params, sched["data_sizes"], keep,
            mix_coefs(self.fl, t)), aux_state
