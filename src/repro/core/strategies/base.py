"""The ServerStrategy interface and the name-keyed strategy registry.

The paper's contribution is the server aggregation rule; everything else
(local SGD, the scheduler, the scan engine) is shared machinery. A
``ServerStrategy`` packages the places an aggregation rule can differ:

  * ``init_state(params)`` — strategy-owned auxiliary server state
    (e.g. the async-AMA ring buffer, fedopt's Adam moments), carried
    through the round loop as a pytree;
  * ``local_grad_transform`` / ``local_steps`` — client-side hooks
    (FedProx's proximal pull + partial work, the FES gradient mask);
  * ``fused_server_update(t, prev_global, client_params, sched,
    aux_state)`` — the server update, a pure jittable function of the
    round index, the previous global model, the stacked client results
    and the round's schedule arrays, through the fused server-plane
    kernel suite (``repro.kernels.server_plane``): ONE pass per round
    (weights, delta accumulation, ring-buffer mix, server-Adam) instead
    of a chain of jnp ops. ``fl.server_plane`` selects "fused"
    (pallas_call on TPU, the jitted flat oracle off-TPU), "ref" (always
    the oracle) or "interpret" (the Pallas body through the
    interpreter — validation only);
  * ``reduced_server_update(...)`` — the same update with the stacked
    client axis pre-reduced, for a mesh whose "client" axis is sharded.

The round engine (``core.round.make_round_step``) is the one place that
chooses among them. The mix family (``strategies.mix.MixStrategy``:
ama, fedavg, fedprox) states both updates once, plus the one that
consumes a comm plane's compressed payload.

Every method is traced inside the jitted round (and inside the fused
``lax.scan`` over rounds), so implementations must be functional: no
Python-level branching on traced values, aux state in/out rather than
mutated.

Adding a new rule is one file: subclass ``MixStrategy`` (a keep mask
and an alpha flag) or ``ServerStrategy`` (a fused and a reduced
update), decorate it with ``@register``, and it becomes reachable from
every entry point (``FederatedSimulation``, the pod round,
``--algorithm`` on the launcher) with no dispatch chain to edit.
"""
from __future__ import annotations

import abc

import jax.numpy as jnp

from repro.configs.base import FLConfig


class ServerStrategy(abc.ABC):
    """Base class: stateless, no grad transform, no mix coefficient."""

    #: registry key; aliases are extra names resolving to the same class
    name: str = ""
    aliases: tuple[str, ...] = ()
    #: True when aux_state is non-empty (changes the flat lowering signature)
    stateful: bool = False
    #: True when the update can consume a comm plane's compressed payload
    #: (``compressed_server_update``); otherwise the round densifies it
    #: before ``fused_server_update``
    consumes_compressed: bool = False

    def __init__(self, fl: FLConfig):
        self.fl = fl

    # ---------------------------------------------------- server side ----
    def init_state(self, params):
        """Strategy-owned auxiliary server state (a pytree; {} if none)."""
        del params
        return {}

    @abc.abstractmethod
    def fused_server_update(self, t, prev_global, client_params, sched,
                            aux_state):
        """One server update through the fused server-plane kernel suite
        (one HBM pass per round; see ``repro.kernels.server_plane``),
        honouring ``fl.server_plane`` ("fused" | "ref" | "interpret").
        ``client_params`` has a leading client axis; ``sched`` is
        {"limited","delayed","delays","data_sizes"}, each (C,).
        Returns (new_global, new_aux_state)."""

    @abc.abstractmethod
    def reduced_server_update(self, t, prev_global, client_params, sched,
                              aux_state):
        """The server update with the stacked client axis PRE-REDUCED.

        Every built-in server plane consumes ``client_params`` only
        through weighted sums over the client axis, so on a mesh whose
        "client" axis is sharded the engine can contract (C, N) -> (N,)
        (``sharding.ctx.reduce_leading``) BEFORE the server math — the
        per-round cross-device collective then moves N, not C x N,
        bytes. Same signature/contract as ``fused_server_update``;
        numerically allclose to (not bit-identical with) the fused
        plane's sequential multiply-add chains, which is why the round
        engine only dispatches here when ``fl.client_reduce`` asks for
        it ("auto" = the active mesh's client axis is > 1)."""

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
        update itself. Rules that take no convex mix keep the base 0;
        the AMA family reports the realized Eq. 5 / Eq. 10 schedule."""
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
