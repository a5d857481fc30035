"""FedOpt: server-side Adam on the aggregated pseudo-gradient (Reddi et
al. 2021's FedAdam, the new extension-point proof for this registry).

The on-time weighted average of client models defines a pseudo-gradient
Delta_t = agg_t - omega_{t-1}; the server applies one Adam step with its
own (lr, b1, b2, tau) instead of AMA's convex mix. Aux state is the
(m, v, step) moment pytree — the same carry mechanism that holds the
async ring buffer, which is exactly what makes this a one-file addition.

Client side it inherits AMA's FES masking, so fedopt composes with the
paper's computation-reduction scheme unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.strategies.ama import AMAStrategy
from repro.core.strategies.base import register


@register
class FedOptStrategy(AMAStrategy):
    name = "fedopt"
    aliases = ()
    stateful = True
    # server-Adam is nonlinear in the aggregated pseudo-gradient (second
    # moment, rsqrt): the round densifies a compressed payload first
    consumes_compressed = False

    def init_state(self, params):
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return {"m": zeros(), "v": zeros(),
                "step": jnp.zeros((), jnp.int32)}

    def mix_coefficient(self, t, sched, aux_state):
        """FedOpt takes an Adam step on the pseudo-gradient rather than
        a convex mix, so the AMA alpha it inherits does not describe
        its update — report 0 like the other non-mix rules."""
        del t, sched, aux_state
        return jnp.float32(0.0)

    def fused_server_update(self, t, prev_global, client_params, sched,
                            aux_state):
        from repro.kernels.server_plane import server_adam_tree
        keep = jnp.logical_not(sched["delayed"]).astype(jnp.float32)
        step = aux_state["step"] + 1
        new_global, m, v = server_adam_tree(
            prev_global, client_params, aux_state["m"], aux_state["v"],
            sched["data_sizes"], keep, self._scalars(step),
            impl=self.server_impl)
        return new_global, {"m": m, "v": v, "step": step}

    def _scalars(self, step):
        """(5,) f32 = [b1, b2, lr, tau, step] (step already incremented)."""
        fl = self.fl
        return jnp.stack([jnp.float32(fl.server_b1),
                          jnp.float32(fl.server_b2),
                          jnp.float32(fl.server_lr),
                          jnp.float32(fl.server_tau),
                          step.astype(jnp.float32)])

    def reduced_server_update(self, t, prev_global, client_params, sched,
                              aux_state):
        """``kernels.ref.server_adam_math`` with the pseudo-gradient
        aggregate pre-reduced over the client axis (one N-byte
        contraction); the Adam moment update is elementwise on (N,)."""
        del t
        from repro.kernels.ref import adam_update, server_adam_coefs
        from repro.sharding.ctx import reduce_leading
        keep = jnp.logical_not(sched["delayed"]).astype(jnp.float32)
        step = aux_state["step"] + 1
        c = server_adam_coefs(sched["data_sizes"], keep,
                              self._scalars(step))
        C = keep.shape[0]
        agg = reduce_leading(client_params, c[:C])
        leaves, treedef = jax.tree.flatten(prev_global)
        outs = [adam_update(p, a, mm, vv, c, C) for p, a, mm, vv in zip(
            leaves, jax.tree.leaves(agg), jax.tree.leaves(aux_state["m"]),
            jax.tree.leaves(aux_state["v"]))]
        new_params, m, v = (treedef.unflatten([o[i] for o in outs])
                            for i in range(3))
        return new_params, {"m": m, "v": v, "step": step}
