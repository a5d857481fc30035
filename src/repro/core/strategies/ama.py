"""Synchronous AMA (paper Eq. 5) as a ServerStrategy.

Server side it mixes the on-time clients with alpha on the Eq. 5
schedule (``strategies.mix``). Client side this is the paper's AMA-FES
pairing: when FES is enabled the gradient of computing-limited devices
is masked to the classifier split (Eq. 2) via ``masked_update``.
"""
from __future__ import annotations

from repro.core.strategies.base import register
from repro.core.strategies.mix import MixStrategy, on_time
from repro.optim.masked import masked_update


@register
class AMAStrategy(MixStrategy):
    name = "ama"
    aliases = ("ama_fes",)   # seed config name; resolve() picks async when
                             # the environment has delays (max_delay > 0)
    adaptive = True

    def keep(self, sched):
        return on_time(sched)

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
