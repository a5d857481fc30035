"""Federated training launcher.

Two configurations of ONE execution engine (``repro.exec``):
  * paper scale (default): K simulated clients — exactly the paper's §V
    experiment with all heterogeneity knobs. The run is driven in
    ``--eval-every``-round chunks through the fused ``lax.scan`` engine
    (batches for a whole chunk staged in one gather, next chunk
    prefetched host-side while the device runs).
  * --pod: C cohorts (silos) over the FL mesh view, each trained every
    round on fresh tokens of its own. The WHOLE run is one fused
    ``lax.scan`` program — one compile, zero per-round dispatch.
    ``build_pod`` builds this path; the chip benchmark's cross-silo
    driver builds through it too.

``--no-scan`` falls back to the bit-identical per-round-jit loop at
either scale (the configuration the engine benchmarks compare against).
Both scales run under ``launch.mesh.engine_mesh``: on one device that is
a degenerate (1, 1, 1) mesh; on a four-chip v5e host the identical
program shards the stacked client axis over the chips.

``--checkpoint`` saves and ``--resume`` restores the FULL round state
{params, t, aux} (async ring buffer, fedopt moments), so continuation
is bit-identical to an uninterrupted run.

``--algorithm`` accepts any name in the server-strategy registry
(repro.core.strategies); ``--env`` any name in the environment registry
(repro.env: bernoulli / gilbert_elliott / bandwidth / trace) and
``--scenario`` any named environment + config binding
(repro.env.scenarios) — adding a strategy/environment/scenario file
extends this launcher with no edits here.

``--metrics-out run.jsonl`` switches on the telemetry plane
(``repro.obs``): per-round staleness/participation/mix/norm/wire series
as schema-versioned JSONL plus a phase-time summary (summarize with
``python -m repro.obs.report run.jsonl``); ``--profile DIR`` wraps the
run in a ``jax.profiler`` trace with named chunk/eval regions.

Examples:
  python -m repro.launch.train --arch paper-cnn --rounds 60 --p-limited 0.5
  python -m repro.launch.train --algorithm fedopt --rounds 5 --eval-every 5
  python -m repro.launch.train --scenario bursty --rounds 40
  python -m repro.launch.train --rounds 20 --checkpoint ck.npz
  python -m repro.launch.train --rounds 20 --resume ck.npz
  python -m repro.launch.train --arch minitron-8b --pod --rounds 3 --reduced
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro import env as env_mod
from repro.checkpoint.io import restore_state, save_state
from repro.configs.base import FLConfig, reduced
from repro.configs.registry import (environment_names, get_arch,
                                    get_scenario, scenario_names)
from repro.core import strategies
from repro.core.round import init_state
from repro.core.simulation import FederatedSimulation
from repro.data.partition import shard_partition
from repro.data.pipeline import VirtualClientShards, build_clients
from repro.env.virtual import is_virtual
from repro.data.synth import make_image_classification, make_lm_tokens
from repro.exec import ChunkRunner
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import engine_mesh
from repro.models.api import build_model
from repro.obs.log import MetricsLogger
from repro.obs.metrics import payload_bytes
from repro.obs.timing import profile_trace, sync_time


def _logger(args) -> MetricsLogger | None:
    return MetricsLogger(args.metrics_out) if args.metrics_out else None


def _print_phases(timer) -> None:
    summary = timer.summary()
    if summary:
        print("phases: " + "  ".join(
            f"{k}={v['seconds']:.2f}s/{v['calls']}"
            for k, v in summary.items()))


def paper_scale(args, fl: FLConfig):
    model = build_model(get_arch(args.arch))
    train, test = make_image_classification(
        n_train=args.n_train, n_test=400, seed=fl.seed)
    if is_virtual(fl):
        # virtual population: clients are arithmetic shard views of the
        # base store — nothing materialised per client, any K
        clients = VirtualClientShards(
            train, fl.num_clients,
            shard_size=max(fl.local_batch_size,
                           args.n_train // min(fl.num_clients, 64)),
            seed=fl.seed)
    else:
        clients = build_clients(
            train,
            shard_partition(train["label"], fl.num_clients, seed=fl.seed))
    logger = _logger(args)
    sim = FederatedSimulation(model, fl, clients, test,
                              use_scan=not args.no_scan,
                              mesh=engine_mesh(fl.clients_per_round),
                              logger=logger)
    if args.resume:
        sim.resume(args.resume)
        print(f"resumed {args.resume} at round {sim.t}")
    with profile_trace(args.profile):
        hist = sim.run(rounds=args.rounds, eval_every=args.eval_every,
                       verbose=True)
    print(f"final: acc={hist.final_accuracy():.4f} "
          f"stability_var={hist.stability_variance():.3f}")
    _print_phases(sim.timer)
    if args.checkpoint:
        sim.save(args.checkpoint)
        print(f"saved {args.checkpoint} (full round state, t={sim.t})")
    if logger is not None:
        logger.close()
        print(f"metrics -> {args.metrics_out} "
              f"(python -m repro.obs.report {args.metrics_out})")
    return sim, hist


def _pod_batches(cfg, fl: FLConfig, args, t0: int, rounds: int):
    """Fresh tokens for every round of ``[t0, t0 + rounds)``: leaves
    (rounds, C, steps, b, ...)."""
    n, C, steps, b, S = rounds, fl.cohorts, fl.local_steps, args.batch, \
        args.seq
    data = make_lm_tokens(n * C * steps * b, S + 1, cfg.vocab_size,
                          n_topics=C, seed=(fl.seed * 1_000_003 + t0)
                          % 2**32)
    tokens = jnp.asarray(
        data["tokens"][:, :S].reshape(n, C, steps, b, S), jnp.int32)
    batch = {"tokens": tokens}
    if cfg.family == "vlm":
        batch["patch_emb"] = jnp.zeros(
            (n, C, steps, b, cfg.num_patches, cfg.vision_dim),
            jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        batch["frame_emb"] = jnp.zeros(
            (n, C, steps, b, cfg.encoder_seq, cfg.d_model),
            jnp.dtype(cfg.dtype))
    return batch


@dataclass
class Pod:
    """What the pod path runs: built once by ``build_pod``."""
    model: object
    fl: FLConfig
    strategy: object
    environment: object
    state: dict
    runner: ChunkRunner


def build_pod(cfg, fl: FLConfig, *, mesh=None, params=None,
              use_scan: bool = True) -> Pod:
    """The cross-silo pod path for ``cfg``: ``fl.cohorts`` silos, each
    trained every round (K = m = C) on fresh data of its own, through a
    ``ChunkRunner`` over ``mesh`` (default ``engine_mesh(C)``, one silo
    per chip where there are C chips). ``params`` replace
    ``model.init``'s weights."""
    model = build_model(cfg)
    # pod scale's stacked client axis is the cohort count — align the
    # config so comm-plane residual state (aux["comm"], sized by
    # fl.clients_per_round in core.round.init_state) matches the (C, ...)
    # client axis the round step actually carries
    C = fl.cohorts
    fl = fl.with_(clients_per_round=C)
    strategy = strategies.resolve(fl)
    state = init_state(model, fl, jax.random.PRNGKey(fl.seed), strategy,
                       params=params)
    environment = env_mod.resolve(fl.with_(num_clients=C))
    runner = ChunkRunner(model, fl, strategy, per_round_batch=True,
                         use_scan=use_scan,
                         mesh=mesh if mesh is not None else engine_mesh(C))
    return Pod(model, fl, strategy, environment, state, runner)


def pod_scale(args, fl: FLConfig, mesh=None):
    """The pod path; ``mesh`` defaults to ``engine_mesh`` over every
    device. Returns (state, per-round metrics, the ChunkRunner)."""
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    pod = build_pod(cfg, fl, mesh=mesh, use_scan=not args.no_scan)
    fl, state, runner = pod.fl, pod.state, pod.runner
    environment = pod.environment
    if args.resume:
        state = restore_state(args.resume, state)
        print(f"resumed {args.resume} at round {int(state['t'])}")
    C = fl.cohorts

    logger = _logger(args)
    if logger is not None:
        logger.header(fl, payload=payload_bytes(state["params"]),
                      resumed_at=int(state["t"]) or None)

    t_start = int(state["t"])
    batches = _pod_batches(runner.model.cfg, fl, args, t_start, args.rounds)
    # timing through obs.timing: perf_counter spans closed by
    # block_until_ready — JAX dispatch is async, so the seed's bare
    # time.time() around run_chunk measured enqueue, not execution
    dt = 0.0
    with profile_trace(args.profile):
        if args.no_scan:
            # stream per-round progress (a multi-hour pod run must not
            # be silent): one-round chunks through the same runner
            rows = []
            for r in range(args.rounds):
                tr, (state, m) = sync_time(
                    runner.run_chunk, state,
                    jax.tree.map(lambda x: x[r:r + 1], batches),
                    environment.batch(t_start + r, 1), scan_ok=False)
                dt += tr
                rows.append(m)
                if logger is not None:
                    logger.rounds(t_start + r, m)
                print(f"round {r}: loss={float(m['loss'][0]):.4f} "
                      f"on_time={int(m['n_on_time'][0])}/{C} "
                      f"({tr:.2f}s)")
            metrics = {k: np.concatenate([m[k] for m in rows])
                       for k in rows[0]}
        else:
            dt, (state, metrics) = sync_time(
                runner.run_chunk, state, batches,
                environment.batch(t_start, args.rounds))
            if logger is not None:
                logger.rounds(t_start, metrics)
            losses = np.asarray(metrics["loss"])
            on_time = np.asarray(metrics["n_on_time"])
            for r in range(args.rounds):
                print(f"round {r}: loss={losses[r]:.4f} "
                      f"on_time={int(on_time[r])}/{C}")
    engine = "per-round jit loop" if args.no_scan else "one fused scan"
    print(f"{args.rounds} rounds ({engine}): {dt:.2f}s total "
          f"({dt/args.rounds*1e3:.1f} ms/round incl. compile)")
    _print_phases(runner.timer)
    if args.checkpoint:
        save_state(args.checkpoint, state)
        print(f"saved {args.checkpoint} (full round state, "
              f"t={int(state['t'])})")
    if logger is not None:
        logger.phases(runner.timer)
        logger.close()
        print(f"metrics -> {args.metrics_out}")
    return state, metrics, runner


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-cnn")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--pod", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced model variant (CPU-sized)")
    ap.add_argument("--algorithm", default="ama_fes",
                    choices=strategies.names())
    ap.add_argument("--env", default="bernoulli", choices=environment_names(),
                    help="environment (channel/device/participation model)")
    ap.add_argument("--scenario", default=None, choices=scenario_names(),
                    help="named environment + config binding; overrides "
                         "--env and the delay knobs (an explicit "
                         "--trace-path still wins)")
    ap.add_argument("--trace-path", default="",
                    help="trace env: .npz schedule to replay "
                         "('' = synthetic mobility trace)")
    ap.add_argument("--no-scan", action="store_true",
                    help="bit-identical per-round jit loop instead of the "
                         "fused chunked scan (both scales)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="paper scale: eval cadence == scan chunk length")
    ap.add_argument("--server-plane", default="fused",
                    choices=("fused", "ref", "interpret"),
                    help="server-update implementation: one fused pass "
                         "per round (default; pallas on TPU, flat oracle "
                         "off-TPU), the flat jnp oracle, or the Pallas "
                         "interpreter (validation only)")
    ap.add_argument("--client-plane", default="masked",
                    choices=("masked", "partitioned"),
                    help="mixed-cohort client execution: one masked "
                         "program for every cohort (default; the "
                         "bit-identity reference) or two programs "
                         "grouped by FES limited-ness — limited cohorts "
                         "never trace the body backward (real Eq. 3 "
                         "computation reduction)")
    ap.add_argument("--population", default="auto",
                    choices=("auto", "dense", "virtual"),
                    help="population realisation: 'auto' keeps the dense "
                         "bit-identical path up to 65536 clients and the "
                         "K-free hashed VirtualPopulation above; "
                         "'dense'/'virtual' force either at any K")
    ap.add_argument("--prefetch-depth", type=int, default=1,
                    help="staged chunks buffered ahead of the device "
                         "(depth + 2 chunks held at once, on the host or, "
                         "from a device sample store, on the device)")
    ap.add_argument("--comm-plane", default="none",
                    choices=("none", "bf16", "q8", "topk"),
                    help="compressed client->server uplink (repro.comm): "
                         "dense f32 (default, bit-identical legacy "
                         "path), bf16 cast (2x), stochastic int8 (~4x) "
                         "or top-k sparsification — all with "
                         "error-feedback residual carried in the round "
                         "state; the bandwidth env and the wire metrics "
                         "consume the real compressed payload size")
    ap.add_argument("--comm-topk-frac", type=float, default=0.01,
                    help="topk plane: surviving fraction of each dtype "
                         "group per round")
    ap.add_argument("--client-reduce", default="auto",
                    choices=("auto", "off", "force"),
                    help="pre-reduce the stacked client axis before the "
                         "server plane ('auto': when the mesh's client "
                         "axis is sharded; collective moves N, not CxN, "
                         "bytes)")
    ap.add_argument("--p-limited", type=float, default=0.25)
    ap.add_argument("--p-delay", type=float, default=0.0)
    ap.add_argument("--max-delay", type=int, default=0)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--clients-per-round", type=int, default=0,
                    help="cohort size m (0 = clients/4, the paper ratio; "
                         "set explicitly for large virtual populations)")
    ap.add_argument("--cohorts", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2, help="pod: per-step batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--n-train", type=int, default=1500)
    ap.add_argument("--metrics-out", default=None,
                    help="write schema-versioned telemetry JSONL here "
                         "(switches on fl.extended_metrics: per-round "
                         "staleness/participation/mix/norm/wire series; "
                         "summarize with python -m repro.obs.report)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the run in jax.profiler.trace(DIR) with "
                         "named chunk/eval regions (TensorBoard trace)")
    ap.add_argument("--checkpoint", default=None,
                    help="save the full round state {params, t, aux} here")
    ap.add_argument("--resume", default=None,
                    help="restore a full round state and continue "
                         "(bit-identical to an uninterrupted run)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def fl_config(args) -> FLConfig:
    """The run's FLConfig from parsed ``build_parser`` arguments."""
    fl = FLConfig(num_clients=args.clients,
                  clients_per_round=(args.clients_per_round
                                     or max(2, args.clients // 4)),
                  local_epochs=2, local_batch_size=25, lr=args.lr,
                  algorithm=args.algorithm, env=args.env,
                  p_limited=args.p_limited,
                  p_delay=args.p_delay, max_delay=args.max_delay,
                  trace_path=args.trace_path,
                  server_plane=args.server_plane,
                  client_plane=args.client_plane,
                  population=args.population,
                  prefetch_depth=args.prefetch_depth,
                  client_reduce=args.client_reduce,
                  comm_plane=args.comm_plane,
                  comm_topk_frac=args.comm_topk_frac,
                  cohorts=args.cohorts, local_steps=args.local_steps,
                  seed=args.seed)
    if args.scenario:
        fl = get_scenario(args.scenario).apply(fl)
        if args.trace_path:       # an explicit recording beats the
            fl = fl.with_(trace_path=args.trace_path)  # scenario default
    if args.metrics_out:
        fl = fl.with_(extended_metrics=True)
    return fl


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    fl = fl_config(args)
    if args.pod:
        pod_scale(args, fl)
    else:
        paper_scale(args, fl)


if __name__ == "__main__":
    main()
