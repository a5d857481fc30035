"""The jitted federated round + the fused multi-round scan engine.

``make_round_step`` is the paper's Algorithm 1 as a single ``train_step``
suitable for pjit on the production mesh: C client cohorts train in
parallel on the "client" mesh axis with NO cross-client collectives
during local steps; the server aggregation — one fused server-plane
kernel pass over the client axis (``strategy.fused_server_update``), or
its pre-reduced form on a sharded client axis — is the only
cross-cohort communication of the round — the paper's
rare-global-aggregation pattern, TPU-native.

``make_train_loop`` goes one step further: it rolls N rounds into one
``jax.lax.scan`` over precomputed schedule arrays, so an entire run
compiles to ONE XLA program — no per-round Python dispatch, no per-round
host sync, and the state carry is donated so the global model is updated
in place.

THE SCHEDULE CONTRACT: every environment in the ``repro.env`` registry
emits stacked ``{selected, limited, delayed, delays, data_sizes}``
arrays via ``Environment.batch(t0, n)`` (row i bit-identical to
``round(t0 + i)``); ``as_scan_scheds`` lifts that numpy dict onto the
device in the exact leaf set the scan body consumes. Any scenario —
i.i.d. Bernoulli, bursty Gilbert-Elliott fading, bandwidth deadlines,
trace replay — therefore drives this engine unchanged.

All algorithm behaviour comes from the ServerStrategy registry
(``repro.core.strategies``); this module contains no per-algorithm or
per-environment branching.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core import strategies
from repro.core.client import (make_fes_local_train, make_local_train,
                               make_partitioned_local_train)
from repro.sharding.ctx import axis_size, constrain_leading, gather_server

#: partitioned-client-plane dispatch arrays (data.pipeline.partition_plan)
#: that ride the schedule dict when fl.client_plane == "partitioned"
PARTITION_KEYS = ("part_full_idx", "part_lim_idx", "part_src_row",
                  "part_from_lim")


def as_scan_scheds(sb: dict) -> dict:
    """Device-ready scan schedules from a stacked ``Environment.batch``
    dict: keeps exactly the leaves the round body consumes (``selected``
    is host-side — it addresses client datasets, not cohort slots) and
    re-types them for the scan carry. Partition-plan arrays (present
    when the partitioned client plane is staged) pass through."""
    out = {"limited": jnp.asarray(sb["limited"]),
           "delayed": jnp.asarray(sb["delayed"]),
           "delays": jnp.asarray(sb["delays"]),
           "data_sizes": jnp.asarray(sb["data_sizes"], jnp.float32)}
    for k in PARTITION_KEYS:
        if k in sb:
            out[k] = jnp.asarray(sb[k])
    return out


def init_state(model, fl: FLConfig, key, strategy=None, params=None):
    """Round-loop carry: global params (``model.init(key)`` unless
    ``params`` are given), round index, strategy aux state
    (async ring buffer, fedopt moments, ... — {} for stateless rules).
    With a comm plane active (``fl.comm_plane != "none"``) the
    error-feedback residual rides the same carry under ``aux["comm"]``
    — one (C, N_g) f32 array per dtype group, C the stacked cohort
    width — so checkpoints/resume carry it like any strategy state."""
    strategy = strategy or strategies.resolve(fl)
    if params is None:
        params = model.init(key)
    aux = strategy.init_state(params)
    from repro import comm
    plane = comm.resolve(fl)
    if plane is not None:
        res = plane.init_residual(params, fl.clients_per_round)
        if res:
            aux = dict(aux)
            aux["comm"] = res
    return {"params": params, "t": jnp.zeros((), jnp.int32), "aux": aux}


def reduces_client_axis(fl: FLConfig) -> bool:
    """Whether the round pre-reduces the stacked client axis before the
    server plane under the ACTIVE mesh (``fl.client_reduce``: "auto"
    where the mesh's client axis is wider than 1, "force" always)."""
    mode = getattr(fl, "client_reduce", "auto")
    if mode not in ("auto", "off", "force"):
        raise ValueError(f"unknown client_reduce {mode!r}; "
                         "expected 'auto' | 'off' | 'force'")
    return mode == "force" or (mode == "auto" and axis_size("client") > 1)


def make_round_step(model, fl: FLConfig, strategy=None):
    """Returns round_step(state, batch, sched) -> (state, metrics).

    batch: pytree with leading (C, steps, b, ...) axes.
    sched: {"limited","delayed","delays","data_sizes"} each (C,); with
    ``fl.client_plane = "partitioned"`` also the ``PARTITION_KEYS``
    dispatch arrays from ``data.pipeline.partition_plan`` (ChunkRunner
    merges them in when it stages a chunk).
    """
    strategy = strategy or strategies.resolve(fl)
    if fl.fes_static:
        plane = make_fes_local_train(model, fl)
        local_train = lambda g, b, sched: plane(g, b, sched["limited"])
    elif getattr(fl, "client_plane", "masked") == "partitioned":
        # two vmapped programs per round, grouped by limited-ness (the
        # staging layer's partition_plan arrays ride in ``sched``) and
        # scattered back into cohort-slot order before the server update
        plane = make_partitioned_local_train(model, fl, strategy)

        def local_train(g, b, sched):
            if "part_src_row" not in sched:
                raise KeyError(
                    "client_plane='partitioned' needs the partition-plan "
                    "arrays in sched — stage through ChunkRunner or merge "
                    "data.pipeline.partition_plan(limited) yourself")
            return plane(g, b, sched)
    elif getattr(fl, "client_plane", "masked") == "masked":
        plane = make_local_train(model, fl, strategy)
        local_train = lambda g, b, sched: plane(g, b, sched["limited"])
    else:
        raise ValueError(f"unknown client_plane {fl.client_plane!r}; "
                         "expected 'masked' or 'partitioned'")

    # extended telemetry (fl.extended_metrics): the per-round series of
    # repro.obs.metrics ride the scan ys — computed from values the round
    # already materializes, so enabling them never changes the params
    # stream (the engine's bit-identity nets gate this)
    extended = bool(getattr(fl, "extended_metrics", False))
    if extended:
        from repro.obs.metrics import payload_bytes, round_metrics

    # comm plane (fl.comm_plane): compress the stacked client deltas
    # BEFORE the server reduction. None for "none" — every branch below
    # is then untaken and the traced program is the pre-comm one
    # byte-for-byte (bit-identity gated by tests/test_comm_plane.py).
    from repro import comm
    comm_plane = comm.resolve(fl)

    # the client, comm and server planes run under named scopes of
    # those names, so every op of the compiled round carries its plane
    # in its ``op_name`` metadata and a device trace can be read by
    # plane; metadata only, the program is otherwise unchanged
    def round_step(state, batch, sched, _tap=None):
        t = state["t"]
        prev_global = state["params"]
        # pre-reduce the stacked client axis when it is actually
        # distributed (fl.client_reduce: "auto" checks the ACTIVE mesh at
        # trace time; "force" for CPU equivalence tests): the weighted
        # delta reduction happens BEFORE the server plane, so the
        # per-round collective moves N, not C x N, bytes. On a 1-device
        # mesh "auto" stays off and the fused plane keeps its
        # bit-identity contract. On that path the server's state lives
        # split over the client axis (sharding.ctx.server_spec): it is
        # gathered whole here for local training, and the reduction
        # scatters the clients' sum back onto the shards.
        reduce = reduces_client_axis(fl)
        train_from = prev_global
        if reduce:
            with jax.named_scope("server_plane"), \
                    jax.named_scope("client_reduce"):
                train_from = gather_server(prev_global)
        with jax.named_scope("client_plane"):
            # stacked client axis over the FL mesh ("client"); no-op
            # off-mesh
            batch = constrain_leading(batch, "client")
            client_params, losses = local_train(train_from, batch, sched)
            client_params = constrain_leading(client_params, "client")
        # compressed uplink: quantize/sparsify the deltas (plus carried
        # error-feedback residual), then hand the SERVER only what the
        # wire would deliver. The residual is comm-plane state, not
        # strategy state — popped here so strategies never see it.
        srv_aux = state["aux"]
        groups = new_res = None
        if comm_plane is not None:
            srv_aux = {k: v for k, v in state["aux"].items() if k != "comm"}
            with jax.named_scope("comm_plane"):
                groups, new_res = comm_plane.compress(
                    t, prev_global, client_params,
                    state["aux"].get("comm", {}))
        # the one place that chooses the server update, from what the
        # round can observe: a sharded client axis pre-reduces; a comm
        # plane feeds its payload straight to a rule that consumes it,
        # and is densified for any other; otherwise ONE fused
        # server-plane pass (fl.server_plane selects the impl)
        if reduce:
            cp = client_params
            if comm_plane is not None:
                with jax.named_scope("comm_plane"):
                    cp = comm_plane.reconstruct(prev_global, groups)
            with jax.named_scope("server_plane"):
                new_params, aux = strategy.reduced_server_update(
                    t, prev_global, cp, sched, srv_aux)
        elif comm_plane is not None and strategy.consumes_compressed:
            with jax.named_scope("server_plane"):
                new_params, aux = strategy.compressed_server_update(
                    t, prev_global, groups, sched, srv_aux)
        else:
            if comm_plane is not None:
                with jax.named_scope("comm_plane"):
                    client_params = comm_plane.reconstruct(prev_global,
                                                           groups)
            with jax.named_scope("server_plane"):
                new_params, aux = strategy.fused_server_update(
                    t, prev_global, client_params, sched, srv_aux)
        if new_res:
            aux = dict(aux)
            aux["comm"] = new_res
        on_time = jnp.logical_not(sched["delayed"])
        metrics = {"loss": jnp.mean(losses),
                   "n_on_time": jnp.sum(on_time.astype(jnp.int32))}
        if extended:
            # the metric taps must OBSERVE the params stream, not
            # participate in it: any extra consumer of the LIVE scan
            # carry (prev params / aux) lets XLA rewrite the update
            # algebra it feeds and shifts the params by 1-2 ulp (and
            # optimization_barrier does not survive this backend's
            # pipeline). ``_tap`` is the shadow copy of the previous
            # round's {params, aux} that make_train_loop threads through
            # a dedicated carry slot — equal by construction, but a
            # separate buffer with no consumers in the round math, so
            # the metrics-off program is untouched. Absent a tap (bare
            # per-round jit outside the engine) the live carry is used:
            # a single-round program has no cross-round fusion to
            # perturb.
            tap = _tap if _tap is not None else {"params": prev_global,
                                                 "aux": state["aux"]}
            metrics.update(round_metrics(
                fl, strategy, t, tap["params"], client_params,
                new_params, sched, tap["aux"],
                payload=payload_bytes(prev_global),
                payload_compressed=(
                    comm_plane.payload_bytes(prev_global)
                    if comm_plane is not None else None)))
        return {"params": new_params, "t": t + 1, "aux": aux}, metrics

    return round_step


def make_train_loop(model, fl: FLConfig, strategy=None, *,
                    per_round_batch: bool = False, donate: bool = True):
    """Fused N-round engine: one XLA program for the whole run.

    Returns train_loop(state, batch, scheds) -> (state, metrics) where
    ``scheds`` leaves carry a leading (n_rounds,) axis (the stacked
    output of ``Environment.batch`` / ``as_scan_scheds``) and metrics come back
    stacked per round. With ``per_round_batch`` the batch pytree also
    carries a leading (n_rounds,) axis (fresh data every round — the
    correctness-equivalence configuration); without it the same batch is
    re-fed each round (the throughput configuration — no O(N) input
    staging). ``donate`` donates the state carry buffers to XLA so the
    global model (and at LLM scale that is the whole HBM budget) is
    updated in place; pass False when the caller needs the input state
    afterwards.

    With ``fl.extended_metrics`` the returned callable takes a fourth
    argument: ``train_loop(state, batch, scheds, tap0)`` where ``tap0``
    is a device COPY of the initial ``{"params", "aux"}`` (separate
    buffers — do not pass the live state arrays, that defeats donation
    and the CSE isolation; see the comment at the extended branch).
    """
    round_step = make_round_step(model, fl, strategy)
    extended = bool(getattr(fl, "extended_metrics", False))

    if extended:
        # shadow-tap plumbing: the telemetry reads the previous round's
        # {params, aux} through a dedicated carry slot seeded from the
        # EXTRA ``tap0`` argument (a caller-side device copy of the
        # initial state — ChunkRunner makes it). The tap must enter the
        # program as its own parameter: seeding it from ``state`` inside
        # the program makes it the same SSA value as the (donated) live
        # carry, and at trip-count-1 XLA value-numbers the two slots
        # back together, re-fusing the metric norms with the server mix
        # and shifting the params by 1 ulp. A distinct parameter cannot
        # be CSE'd away, so the live carry keeps exactly the consumer
        # set of the metrics-off program — the bit-identity contract
        # (see round_step).
        def train_loop_ext(state, batch, scheds, tap0):
            def body(carry, xs):
                st, tap = carry
                b, sc = xs if per_round_batch else (batch, xs)
                new_st, m = round_step(st, b, sc, tap)
                return (new_st, {"params": new_st["params"],
                                 "aux": new_st["aux"]}), m
            xs = (batch, scheds) if per_round_batch else scheds
            (state, _), metrics = jax.lax.scan(body, (state, tap0), xs)
            return state, metrics
        return jax.jit(train_loop_ext,
                       donate_argnums=(0,) if donate else ())

    def train_loop(state, batch, scheds):
        if per_round_batch:
            def body(st, xs):
                b, sc = xs
                return round_step(st, b, sc)
            return jax.lax.scan(body, state, (batch, scheds))

        def body(st, sc):
            return round_step(st, batch, sc)
        return jax.lax.scan(body, state, scheds)

    return jax.jit(train_loop, donate_argnums=(0,) if donate else ())


def make_train_step_for_lowering(model, fl: FLConfig):
    """Flat-signature variant for .lower(): (params, [aux,] t, batch,
    sched) -> same. Keeps the dry-run input_specs simple. Off-TPU the
    fused server plane lowers as the flat oracle (see
    ``kernels.server_plane._route``), so the dry-run's HLO cost analysis
    sees the real fused op sequence, not interpreter emulation."""
    from repro import comm
    strategy = strategies.resolve(fl)
    round_step = make_round_step(model, fl, strategy)
    plane = comm.resolve(fl)

    # a comm plane with error feedback makes aux non-empty even for
    # stateless strategies (the residual rides aux["comm"])
    if strategy.stateful or (plane is not None and plane.error_feedback):
        def step(params, aux, t, batch, sched):
            state = {"params": params, "t": t, "aux": aux}
            out, metrics = round_step(state, batch, sched)
            return out["params"], out["aux"], metrics
        return step

    def step(params, t, batch, sched):
        state = {"params": params, "t": t, "aux": {}}
        out, metrics = round_step(state, batch, sched)
        return out["params"], metrics
    return step
