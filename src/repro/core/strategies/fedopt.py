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

from repro.core.ama import normalize_weights, weighted_client_sum
from repro.core.strategies.ama import AMAStrategy
from repro.core.strategies.base import register


@register
class FedOptStrategy(AMAStrategy):
    name = "fedopt"
    aliases = ()
    stateful = True

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

    def aggregate(self, t, prev_global, client_params, sched, aux_state):
        del t  # fedopt keys its schedule on its own step counter
        fl = self.fl
        on_time = jnp.logical_not(sched["delayed"])
        w, tot = normalize_weights(sched["data_sizes"], on_time)
        agg = weighted_client_sum(client_params, w)
        agg = jax.tree.map(lambda a, p: jnp.where(tot > 0, a, p),
                           agg, prev_global)

        delta = jax.tree.map(
            lambda a, p: a.astype(jnp.float32) - p.astype(jnp.float32),
            agg, prev_global)
        step = aux_state["step"] + 1
        m = jax.tree.map(lambda mm, d: fl.server_b1 * mm
                         + (1.0 - fl.server_b1) * d, aux_state["m"], delta)
        v = jax.tree.map(lambda vv, d: fl.server_b2 * vv
                         + (1.0 - fl.server_b2) * d * d, aux_state["v"], delta)
        sf = step.astype(jnp.float32)
        bc1 = 1.0 - fl.server_b1 ** sf
        bc2 = 1.0 - fl.server_b2 ** sf
        update = jax.tree.map(
            lambda mm, vv: (mm / bc1)
            / (jnp.sqrt(vv / bc2) + fl.server_tau), m, v)

        if fl.use_kernel:
            # prev + lr*update == 1.0*prev + sum_k w_k stacked_k with
            # K=1, w=[lr]: the general fused-mix kernel, not a special case
            from repro.kernels.ops import ama_mix_tree
            stacked = jax.tree.map(lambda u: u[None], update)
            new_global = ama_mix_tree(prev_global, stacked, 1.0,
                                      jnp.full((1,), fl.server_lr))
        else:
            new_global = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32)
                              + fl.server_lr * u).astype(p.dtype),
                prev_global, update)
        return new_global, {"m": m, "v": v, "step": step}

    def compressed_server_update(self, t, prev_global, groups, sched,
                                 aux_state):
        """Server-Adam is nonlinear in the aggregated pseudo-gradient
        (second moment, rsqrt), so the linear compressed mix this class
        inherits from AMA does not describe it — revert to
        NotImplemented; the round engine densifies the payload and
        dispatches the fused Adam plane."""
        del t, prev_global, groups, sched, aux_state
        return NotImplemented

    def fused_server_update(self, t, prev_global, client_params, sched,
                            aux_state):
        if self.server_impl == "legacy":
            return self.aggregate(t, prev_global, client_params, sched,
                                  aux_state)
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
