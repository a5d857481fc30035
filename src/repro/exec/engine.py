"""The unified chunked-scan execution engine.

One engine, two configurations — the paper-scale §V simulation
(K simulated clients, real per-client data staged per round) and the
pod-scale cohort run (C cohorts over the FL mesh) are the SAME round
path with a different data plane:

  * ``ChunkRunner`` drives rounds in chunks through the fused
    ``core.round.make_train_loop`` scan (donated carry, one XLA dispatch
    per chunk), with a ``use_scan=False`` per-round-jit fallback that is
    bit-identical (the ``--no-scan`` safety net — see
    tests/test_engine.py);
  * ``SimulationEngine`` adds the vectorized data plane
    (``data.pipeline.stage_chunk`` — one gather per chunk of rounds,
    from a sample store kept on the device where it and the chunks in
    flight fit, else on the host; the next chunk is staged on a host
    thread while the current chunk runs on device), the jitted batched
    eval (``exec.evals.Evaluator``) at an ``eval_every`` cadence, full
    round-state checkpointing ({params, t, aux}: async ring buffer,
    fedopt moments, ...) and the ``History`` stability metrics;
  * both run under the FL mesh (``launch.mesh.engine_mesh``) so the
    stacked client axis of params and batches is sharded on a pod and a
    degenerate no-op on this CPU container — the identical program at
    both scales.

Everything round-path-schedulable comes in through the two registries:
the server rule is a ``ServerStrategy``, the world an ``Environment``;
the engine owns only data movement, chunking and evaluation. The server
side of every round — staleness weights, weighted delta accumulation,
ring-buffer mix, server-Adam — dispatches as ONE fused server-plane
kernel call (``ServerStrategy.fused_server_update`` →
``repro.kernels.server_plane``) on both the chunked-scan path and the
``--no-scan`` per-round path; ``fl.server_plane`` selects the impl
("fused" | "ref" | "interpret").
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import env as env_mod
from repro.checkpoint.io import restore_state, save_state
from repro.configs.base import FLConfig
from repro.core import strategies
from repro.core.round import (as_scan_scheds, init_state, make_train_loop,
                              reduces_client_axis)
from repro.data.pipeline import (ChunkPrefetcher, DeviceStore,
                                 partition_plan, place_store, stage_chunk)
from repro.exec.evals import Evaluator
from repro.obs.metrics import stability_stats
from repro.obs.timing import PhaseTimes


@dataclass
class History:
    """Per-run metric record. ``test_acc[i]`` was measured after
    ``eval_rounds[i]`` rounds (ABSOLUTE indices — a resumed run
    continues the count), so the stability window is a span of ROUNDS
    regardless of the eval cadence: with ``eval_every=5``,
    ``stability_variance(last=50)`` covers the 10 eval points of the
    last 50 rounds, not 50 eval points spanning 250 rounds (the seed's
    silent unit confusion). The round-window math lives in
    ``repro.obs.metrics.stability_stats`` — the report CLI calls the
    same function on a metrics JSONL, which is why the two always
    agree exactly."""

    test_acc: list = field(default_factory=list)
    test_loss: list = field(default_factory=list)
    train_loss: list = field(default_factory=list)
    eval_rounds: list = field(default_factory=list)

    def stability_variance(self, last: int = 50) -> float:
        """Paper's stability metric: variance of test accuracy over the
        last ``last`` ROUNDS (in percentage points squared)."""
        return stability_stats(self.eval_rounds, self.test_acc,
                               last)["stability_variance"]

    def final_accuracy(self, last: int = 50) -> float:
        return stability_stats(self.eval_rounds, self.test_acc,
                               last)["final_accuracy"]


def _shape_of(x):
    """ShapeDtypeStruct of an argument; a committed array keeps its
    sharding, an uncommitted one is placed by the program."""
    x = jnp.asarray(x)
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if x.committed else None)


class ChunkRunner:
    """The unified round path: N rounds per call, fused scan or fallback.

    ``per_round_batch=True`` (paper scale) scans a fresh
    (n, C, steps, b, ...) batch row per round; ``False`` (pod scale)
    re-feeds one (C, steps, b, ...) batch every round. ``use_scan=False``
    replays the identical rounds one at a time (scan of length 1) — the
    bit-identical ``--no-scan`` configuration. A mesh makes the
    engine span a pod: the call runs under it, activating the
    stacked-client-axis constraints inside ``make_round_step``.
    """

    def __init__(self, model, fl: FLConfig, strategy=None, *,
                 per_round_batch: bool = True, use_scan: bool = True,
                 mesh=None, donate: bool = True, timer=None):
        self.model, self.fl = model, fl
        self.strategy = strategy or strategies.resolve(fl)
        self.per_round_batch = per_round_batch
        self.use_scan = use_scan
        self.mesh = mesh
        # ONE jitted train_loop serves the fused chunk scan AND the
        # per-round fallback (scan of length 1): jax.jit specialises per
        # chunk-length shape under the same callable, and sharing the
        # callable keeps the two paths structurally identical
        self._loop = None
        self._donate = donate
        # telemetry: phase wall-clock (repro.obs.timing.PhaseTimes).
        # The first dispatch of a given chunk length is a fresh jit
        # specialisation, so its wall time books under "compile"
        # (trace + XLA compile + first execution); steady-state chunks
        # book under "scan_dispatch" / "round_dispatch"
        self.timer = timer if timer is not None else PhaseTimes()
        self._compiled: set = set()
        self._last_call = None      # argument shapes of the last dispatch

    def _dispatch(self, loop, state, batch, scheds, n: int, *,
                  scan: bool):
        key = (n, self.per_round_batch)
        phase = ("compile" if key not in self._compiled
                 else ("scan_dispatch" if scan and n > 1
                       else "round_dispatch"))
        self._compiled.add(key)
        with self.timer.phase(phase, region=f"train_chunk_n{n}") as span:
            args = (state, batch, scheds)
            if getattr(self.fl, "extended_metrics", False):
                # extended telemetry: the loop takes a shadow tap — a
                # device COPY of the entering {params, aux} (separate
                # buffers keep donation usable and keep XLA from
                # value-numbering the tap onto the live carry; see
                # make_train_loop). The copy is O(model), once per
                # dispatch — noise next to the chunk's training work.
                args += (jax.tree.map(jnp.copy, {"params": state["params"],
                                                 "aux": state["aux"]}),)
            self._last_call = jax.tree.map(_shape_of, args)
            out = loop(*args)
            span.sync(out)
        return out

    def lower_last(self):
        """The program of the last dispatched chunk, lowered again from
        the argument shapes it ran with: ``.compile().as_text()`` shows
        which kernels and collectives a round really runs."""
        with self._ctx():
            return self._train_loop().lower(*self._last_call)

    def _place(self, state):
        """Where the round reduces over a sharded client axis, the
        server's state lives split over it (``sharding.ctx.
        server_spec``), as the program returns it: placed so before the
        first dispatch, every dispatch takes one sharding (a no-op once
        it is)."""
        if self.mesh is None or not reduces_client_axis(self.fl):
            return state
        from repro.sharding.ctx import server_shardings
        return {**state, "params": jax.device_put(
            state["params"], server_shardings(state["params"], self.mesh))}

    def _ctx(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _train_loop(self):
        if self._loop is None:
            self._loop = make_train_loop(
                self.model, self.fl, self.strategy,
                per_round_batch=self.per_round_batch, donate=self._donate)
        return self._loop

    def run_chunk(self, state, batch, sched_batch: dict, *,
                  scan_ok: bool = True):
        """(state, batch, Environment.batch dict) -> (state, metrics).

        ``batch`` leaves: (n, C, steps, b, ...) when per_round_batch
        else (C, steps, b, ...); numpy or device arrays. ``metrics``
        come back as numpy arrays with a leading (n,) axis.
        ``scan_ok=False`` routes an off-cadence chunk (a tail shorter
        than ``eval_every``, a standalone single round) through the
        bit-identical per-round path instead of compiling a fresh
        scan program for its one-off length. That path is a SCAN OF
        LENGTH 1 per round, not a bare jitted round step: XLA compiles
        a ``lax.scan`` body as its own computation, so the per-round
        program and the chunked scan contract multiply-add chains
        identically — a bare per-round jit re-fuses the fused
        server-plane chains with the surrounding round and drifts by
        1-2 ulp, which the bit-identity nets (and resume across chunk
        boundaries) do not tolerate.
        """
        if (getattr(self.fl, "client_plane", "masked") == "partitioned"
                and not self.fl.fes_static
                and "part_src_row" not in sched_batch):
            # partitioned client plane: group the chunk's cohorts by
            # limited-ness host-side (the staging layer's other half);
            # the plan is chunk-level so the fused scan and the
            # per-round fallback replay the IDENTICAL dispatch
            sched_batch = {**sched_batch,
                           **partition_plan(sched_batch["limited"])}
        scheds = as_scan_scheds(sched_batch)
        n = int(jax.tree.leaves(scheds)[0].shape[0])
        # the copy completes before the dispatch, so "h2d" is the copy
        # alone and the train-loop phase the program alone; a batch
        # already on the device copies nothing and "h2d" waits for it
        with self.timer.phase("h2d") as span:
            batch = span.sync(jax.tree.map(jnp.asarray, batch))
        with self._ctx():
            state = self._place(state)
            loop = self._train_loop()
            if self.use_scan and scan_ok:
                state, metrics = self._dispatch(loop, state, batch,
                                                scheds, n, scan=True)
            else:
                rows = []
                for r in range(n):
                    b = (jax.tree.map(lambda x: x[r:r + 1], batch)
                         if self.per_round_batch else batch)
                    sc = jax.tree.map(lambda x: x[r:r + 1], scheds)
                    state, m = self._dispatch(loop, state, b, sc, 1,
                                              scan=False)
                    rows.append(jax.tree.map(lambda x: x[0], m))
                metrics = {k: jnp.stack([m[k] for m in rows])
                           for k in rows[0]}
        return state, jax.tree.map(np.asarray, metrics)


class SimulationEngine:
    """Paper-scale federated simulation on the chunked-scan engine.

    Drives ``eval_every``-round chunks through ``ChunkRunner`` over any
    registered environment: schedules from ``Environment.batch``, client
    batches staged in one gather per chunk (``stage_chunk``) with the
    next chunk prefetched on a host thread, eval through the jitted
    batched ``Evaluator``. Each ``run`` places the shared sample store
    for its chunks (``_place_store``): on the device (replicated over
    ``mesh``) where it fits with the chunks staging holds, so staging
    gathers there, else on the host (``data.pipeline.place_store``).
    ``use_scan=False`` is the per-round fallback (bit-identical; the
    refactor's safety net).
    """

    def __init__(self, model, fl: FLConfig, clients, test_data,
                 eval_fn=None, eval_batch: int = 512, environment=None,
                 use_scan: bool = True, mesh=None, prefetch: bool = True,
                 donate: bool = True, logger=None):
        self.model = model
        self.fl = fl
        # clients: a dense list[ClientDataset] OR a VirtualClientShards
        # (K-free streamed staging — client shards are arithmetic views
        # of one base store, nothing materialised per client)
        self.clients = clients
        self._streamed = hasattr(clients, "shard_indices")
        self.test_data = test_data
        # any registered environment (fl.env); data sizes feed the
        # |D_i| aggregation weights through the schedule contract —
        # as a dense (K,) vector for a client list, as a callable for
        # virtual shards (a (K,) vector is what we are avoiding)
        self.env = environment or env_mod.resolve(
            fl, data_sizes=(clients.client_sizes if self._streamed else
                            np.array([len(c) for c in clients],
                                     np.float32)))
        self.strategy = strategies.resolve(fl)
        # donate=True updates the carry in place on accelerator backends,
        # which also invalidates params references held from BEFORE a
        # run() call; pass False to keep pre-run references alive there
        self.runner = ChunkRunner(model, fl, self.strategy,
                                  per_round_batch=True, use_scan=use_scan,
                                  mesh=mesh, donate=donate)
        self._eval_fn = eval_fn
        self._evaluator = (None if eval_fn is not None
                           else Evaluator(model, test_data, eval_batch))
        self.prefetch = prefetch
        # telemetry plane: one PhaseTimes spans runner + data plane +
        # eval + checkpointing; an optional MetricsLogger (repro.obs.log)
        # receives per-round rows, eval points and the phase summary
        self.timer = PhaseTimes()
        self.runner.timer = self.timer
        self.logger = logger
        self.data = clients.data if self._streamed else clients[0].data
        if not self._streamed and any(c.data is not self.data
                                      for c in clients):
            raise ValueError(
                "the chunked data plane stages every client from ONE "
                "shared sample store (build clients with "
                "data.pipeline.build_clients(data, partition))")
        # where staging gathers from: the host until a run places it
        self._store = self.data
        self.state = init_state(model, fl, jax.random.PRNGKey(fl.seed),
                                self.strategy)

    # engine state — the full round carry {params, t, aux} ---------------
    @property
    def params(self):
        return self.state["params"]

    @property
    def t(self) -> int:
        return int(self.state["t"])

    @property
    def aux(self):
        return self.state["aux"]

    def save(self, path: str) -> None:
        """Checkpoint the WHOLE round state (params, round index, aux:
        async ring buffer, fedopt moments, ...)."""
        with self.timer.phase("checkpoint"):
            save_state(path, self.state)

    def resume(self, path: str) -> None:
        """Bit-identical continuation: restore {params, t, aux}; staging
        and schedules are pure in t, so the next chunk starts exactly
        where the checkpointed run left off."""
        self.state = restore_state(path, self.state)

    # ------------------------------------------------------------------
    def _steps_per_round(self) -> int:
        n_min = (self.clients.min_size if self._streamed
                 else min(len(c) for c in self.clients))
        per_epoch = max(1, n_min // self.fl.local_batch_size)
        return self.fl.local_epochs * per_epoch

    def _place_store(self, n_rounds: int) -> None:
        """Place the sample store for chunks of up to ``n_rounds``
        rounds (``data.pipeline.place_store``). Staging holds
        ``prefetch_depth + 2`` chunks at once: the running one, the
        queue's and the one the worker waits to queue; without prefetch
        two, the running one and the next. A store already on the
        device stays where it still fits, and is dropped where not."""
        in_flight = (max(getattr(self.fl, "prefetch_depth", 1), 1) + 2
                     if self.prefetch else 2)
        samples = (n_rounds * self.fl.clients_per_round
                   * self._steps_per_round() * self.fl.local_batch_size)
        placed = self._store if isinstance(self._store, DeviceStore) else None
        self._store = place_store(self.data, samples, in_flight,
                                  self.runner.mesh, placed)

    def _stage(self, t0: int, n: int):
        # runs on the prefetcher's worker thread during overlapped
        # execution — PhaseTimes is thread-safe, so "stage" seconds
        # accumulate either way (they OVERLAP device phases by design);
        # "stage_cpu" books this thread's CPU seconds inside "stage"
        with self.timer.phase("stage", region=f"stage_t{t0}", cpu=True):
            sb = self.env.batch(t0, n)
            batch = stage_chunk(self._store, self.clients, sb["selected"],
                                self.fl.seed, t0,
                                self._steps_per_round(),
                                self.fl.local_batch_size, timer=self.timer)
        return sb, batch

    def run_round(self) -> float:
        """One round through the engine (a chunk of 1; per-round step —
        no one-off scan program for a standalone round)."""
        self._place_store(1)
        sb, batch = self._stage(self.t, 1)
        self.state, metrics = self.runner.run_chunk(self.state, batch, sb,
                                                    scan_ok=False)
        return float(metrics["loss"][0])

    def evaluate(self) -> tuple[float, float]:
        with self.timer.phase("eval"):
            if self._eval_fn is not None:
                return self._eval_fn(self.state["params"],
                                     self.test_data)
            return self._evaluator(self.state["params"])

    def run(self, rounds: int | None = None, eval_every: int = 1,
            verbose: bool = False) -> History:
        hist = History()
        rounds = rounds or self.fl.rounds
        t0, end = self.t, self.t + rounds
        if self.logger is not None:
            from repro.obs.metrics import payload_bytes
            self.logger.header(self.fl,
                               payload=payload_bytes(self.params),
                               resumed_at=t0 if t0 else None)
        # chunk boundaries sit on ABSOLUTE multiples of eval_every, so a
        # resumed run evaluates at the same global rounds as the
        # uninterrupted run it continues (off-cadence head/tail chunks
        # replay through the per-round step, no one-off scan compile)
        chunks, t = [], t0
        while t < end:
            n = min((t // eval_every + 1) * eval_every, end) - t
            chunks.append((t, n))
            t += n
        self._place_store(max((n for _, n in chunks), default=1))
        prefetcher = (ChunkPrefetcher(lambda c: self._stage(*c), chunks,
                                      depth=getattr(self.fl,
                                                    "prefetch_depth", 1))
                      if self.prefetch else None)
        staged = (iter(prefetcher) if prefetcher is not None
                  else (self._stage(*c) for c in chunks))
        try:
            for t, n in chunks:
                # the round waits for its staged chunk; without prefetch
                # this span holds the chunk's whole inline staging
                with self.timer.phase("stage_wait"):
                    sb, batch = next(staged)
                self.state, metrics = self.runner.run_chunk(
                    self.state, batch, sb, scan_ok=(n == eval_every))
                del batch       # free the chunk before staging takes more
                hist.train_loss.extend(float(x) for x in metrics["loss"])
                if self.logger is not None:
                    self.logger.rounds(t, metrics)
                if (t + n) % eval_every == 0:    # partial chunks: no eval
                    acc, loss = self.evaluate()
                    hist.test_acc.append(acc)
                    hist.test_loss.append(loss)
                    hist.eval_rounds.append(t + n)
                    if self.logger is not None:
                        self.logger.eval(t + n, acc, loss)
                    done = t + n - t0
                    if verbose and done % 10 == 0:
                        print(f"  round {done:4d} "
                              f"train_loss={hist.train_loss[-1]:.4f} "
                              f"test_acc={acc:.4f}")
        finally:
            if prefetcher is not None:
                prefetcher.close()       # abandoned mid-run: release the
            if self.logger is not None:  # worker + buffered chunks
                self.logger.phases(self.timer)
        return hist
