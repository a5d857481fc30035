"""Naive FL baseline (the paper's "FedAvg"): weighted average of the
clients that both finished (not computing-limited) and arrived on time;
no mixing with the previous model, no staleness handling — the alpha=0
corner of the mix (``strategies.mix``)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.strategies.base import register
from repro.core.strategies.mix import MixStrategy, on_time


@register
class FedAvgStrategy(MixStrategy):
    name = "fedavg"
    adaptive = False

    def keep(self, sched):
        return jnp.logical_and(on_time(sched),
                               jnp.logical_not(sched["limited"]))
