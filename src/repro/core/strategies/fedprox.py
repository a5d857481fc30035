"""FedProx baseline (paper Eq. 4): proximal gradient pull toward the
global model plus "partial work" — computing-limited devices run a
fraction of the local steps instead of masking gradients. Server side it
is the on-time weighted average, the alpha=0 corner of the mix
(``strategies.mix``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.strategies.base import register
from repro.core.strategies.mix import MixStrategy, on_time


@register
class FedProxStrategy(MixStrategy):
    name = "fedprox"
    adaptive = False

    def keep(self, sched):
        return on_time(sched)

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
