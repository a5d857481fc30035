"""The ServerStrategy interface and the name-keyed strategy registry.

The paper's contribution is the server aggregation rule; everything else
(local SGD, the scheduler, the scan engine) is shared machinery. A
``ServerStrategy`` packages the three places an aggregation rule can
differ:

  * ``init_state(params)`` — strategy-owned auxiliary server state
    (e.g. the async-AMA ring buffer, fedopt's Adam moments), carried
    through the round loop as a pytree;
  * ``local_grad_transform`` / ``local_steps`` — client-side hooks
    (FedProx's proximal pull + partial work, the FES gradient mask);
  * ``aggregate(t, prev_global, client_params, sched, aux_state)`` —
    the server update itself, a pure jittable function of the round
    index, the previous global model, the stacked client results and the
    round's schedule arrays;
  * ``fused_server_update(...)`` — the same update through the fused
    server-plane kernel suite (``repro.kernels.server_plane``): ONE
    Pallas pass per round (weights, delta accumulation, ring-buffer
    mix, server-Adam all in-kernel) instead of a chain of jnp ops. The
    round engine (``core.round.make_round_step``) dispatches here;
    ``fl.server_plane`` selects "fused" (pallas_call on TPU, the jitted
    flat oracle off-TPU), "ref" (always the oracle), "interpret" (the
    Pallas body through the interpreter — validation only) or "legacy"
    (the original per-leaf ``aggregate`` chain).

Every method is traced inside the jitted round (and inside the fused
``lax.scan`` over rounds), so implementations must be functional: no
Python-level branching on traced values, aux state in/out rather than
mutated.

Adding a new rule is one file: subclass ``ServerStrategy``, decorate it
with ``@register``, and it becomes reachable from every entry point
(``FederatedSimulation``, the pod round, ``--algorithm`` on the
launcher) with no dispatch chain to edit.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import FLConfig


class ServerStrategy:
    """Base class: FedAvg-shaped defaults, stateless, no grad transform."""

    #: registry key; aliases are extra names resolving to the same class
    name: str = ""
    aliases: tuple[str, ...] = ()
    #: True when aux_state is non-empty (changes the flat lowering signature)
    stateful: bool = False

    def __init__(self, fl: FLConfig):
        self.fl = fl

    # ---------------------------------------------------- server side ----
    def init_state(self, params):
        """Strategy-owned auxiliary server state (a pytree; {} if none)."""
        del params
        return {}

    def aggregate(self, t, prev_global, client_params, sched, aux_state):
        """One server update. ``client_params`` has a leading client axis;
        ``sched`` is {"limited","delayed","delays","data_sizes"}, each (C,).
        Returns (new_global, new_aux_state)."""
        raise NotImplementedError

    def fused_server_update(self, t, prev_global, client_params, sched,
                            aux_state):
        """One server update through the fused server-plane kernel suite
        (one HBM pass per round; see ``repro.kernels.server_plane``).
        Same signature and contract as ``aggregate``. The base fallback
        routes to ``aggregate`` so out-of-tree strategies keep working;
        built-ins override it and honour ``fl.server_plane``
        ("fused" | "ref" | "legacy")."""
        return self.aggregate(t, prev_global, client_params, sched,
                              aux_state)

    def compressed_server_update(self, t, prev_global, groups, sched,
                                 aux_state):
        """The server update consuming a comm plane's COMPRESSED payload
        directly — fused dequantize-accumulate, no dense (C, N) f32
        intermediate.

        ``groups`` is ``repro.comm``'s flat per-dtype-group payload list
        (``[(leaf_idxs, payload)]``, see
        ``kernels.server_plane.server_mix_compressed_tree``). The mix
        family overrides this; strategies whose update is not linear in
        the stacked deltas (async ring buffer, server-Adam) return
        ``NotImplemented`` (the base default) and the round engine
        densifies via ``CommPlane.reconstruct`` before their fused
        update — same numbers, one extra dense pass."""
        del t, prev_global, groups, sched, aux_state
        return NotImplemented

    def reduced_server_update(self, t, prev_global, client_params, sched,
                              aux_state):
        """The server update with the stacked client axis PRE-REDUCED.

        Every built-in server plane consumes ``client_params`` only
        through weighted sums over the client axis, so on a mesh whose
        "client" axis is sharded the engine can contract (C, N) -> (N,)
        (``sharding.ctx.reduce_leading``) BEFORE the server math — the
        per-round cross-device collective then moves N, not C x N,
        bytes. Same signature/contract as ``aggregate``; numerically
        allclose to (not bit-identical with) the fused plane's
        sequential multiply-add chains, which is why the round engine
        only dispatches here when ``fl.client_reduce`` asks for it
        ("auto" = the active mesh's client axis is > 1). Return
        ``NotImplemented`` (the base default) to always use the fused
        plane."""
        del t, prev_global, client_params, sched, aux_state
        return NotImplemented

    @property
    def server_impl(self) -> str:
        """The configured server-plane implementation."""
        return getattr(self.fl, "server_plane", "fused")

    # ---------------------------------------------------- telemetry ----
    def mix_coefficient(self, t, sched, aux_state):
        """The EFFECTIVE previous-model mix coefficient alpha of this
        round's server update — the telemetry plane's ``alpha_eff``
        series (``repro.obs.metrics.round_metrics``). Pure, traced
        inside the round (and the fused scan), must not touch the
        update itself. Pure weighted-average rules (fedavg/fedprox)
        keep the base 0; the AMA family reports the realized Eq. 5 /
        Eq. 10 schedule."""
        del t, sched, aux_state
        return jnp.float32(0.0)

    # ---------------------------------------------------- client side ----
    def local_grad_transform(self, grads, params, global_params, fes_mask,
                             limited):
        """Per-step gradient hook inside local training (identity here)."""
        del params, global_params, fes_mask, limited
        return grads

    def local_steps(self, n_steps: int, limited):
        """Number of active local steps for a client; ``n_steps`` is the
        static step count, ``limited`` the (traced) FES flag."""
        del limited
        return jnp.int32(n_steps)

    # -------------------------------------- partitioned client plane ----
    @property
    def limited_mode(self) -> str:
        """How a computing-limited cohort executes under the PARTITIONED
        client plane (``fl.client_plane = "partitioned"``):

          * ``"full"`` — the same gradients an unlimited cohort takes
            (the base default: ``local_grad_transform`` applies no FES
            mask, so the masked plane trains limited cohorts fully too);
          * ``"classifier"`` — classifier-only differentiation: the body
            backward is never traced (AMA-FES, paper Eq. 3).
        """
        return "full"

    def static_local_steps(self, n_steps: int) -> int:
        """Python-int local-step budget of a LIMITED cohort — the static
        scan length of the partitioned plane's limited program. Must
        agree with ``local_steps(n_steps, limited=True)`` (the masked
        plane's traced cutoff) for the two planes to be equivalent."""
        return n_steps


def reduced_mix_update(prev_global, client_params, sizes, keep, coefs):
    """The mix-family server plane (``kernels.ref.server_mix_math``)
    with the client axis pre-reduced: out = a_eff*prev + sum_k
    (beta*w_k)*x_k, where the weighted sum is ONE ``reduce_leading``
    contraction (an N-byte collective on a sharded mesh). Same
    arguments as ``server_mix_tree``; shared by ama/fedavg/fedprox,
    which differ only in ``keep`` and the alpha schedule."""
    import jax

    from repro.kernels.ref import server_mix_coefs
    from repro.sharding.ctx import reduce_leading
    c = server_mix_coefs(sizes, keep, coefs)        # [a_eff, beta*w]
    red = reduce_leading(client_params, c[1:])
    return jax.tree.map(
        lambda p, r: (p.astype(jnp.float32) * c[0] + r).astype(p.dtype),
        prev_global, red)


_REGISTRY: dict[str, type[ServerStrategy]] = {}


def register(cls: type[ServerStrategy]) -> type[ServerStrategy]:
    """Class decorator: file-local registration under name + aliases."""
    assert cls.name, cls
    for key in (cls.name,) + tuple(cls.aliases):
        assert key not in _REGISTRY or _REGISTRY[key] is cls, key
        _REGISTRY[key] = cls
    return cls


def names() -> list[str]:
    """All registered strategy names (aliases included), sorted."""
    return sorted(_REGISTRY)


def get(name: str) -> type[ServerStrategy]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown strategy {name!r}; "
                       f"registered: {names()}") from None


def resolve(fl: FLConfig) -> ServerStrategy:
    """Instantiate the strategy for a config. The AMA family upgrades to
    the asynchronous variant when the environment has delays
    (``max_delay > 0``), preserving the seed's behaviour where
    ``algorithm="ama_fes", max_delay=5`` meant async AMA."""
    cls = get(fl.algorithm)
    if fl.max_delay > 0 and cls.name == "ama":
        cls = get("async_ama")
    return cls(fl)
