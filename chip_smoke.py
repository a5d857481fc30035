"""Run the federated round path on a TPU and check what comes out.

    python chip_smoke.py [--seed N]     # one chip: phases a-g below
    python chip_smoke.py --chips 4      # four chips: the sharded pod path

One process does everything (a chip belongs to one process at a time).
Every phase goes through the functions ``repro.launch.train`` and
``repro.launch.serve`` run from their ``main()``, with random weights and
synthetic data drawn from ``--seed``:

  a  the paper setting at full width: paper CNN, K=50 clients, m=10 per
     round, p_limited=0.25, ama_fes, bernoulli environment, 3 rounds
     with eval after each;
  b  as a, async_ama with max_delay=15, p_delay=0.3 (a 16-slot ring);
  c  as a, fedopt (server Adam);
  d  as a, with the q8 uplink, then with the top-k uplink;
  e  as a, p_limited=0.5 on the partitioned client plane;
  f  the pod path: reduced minitron-8b, 2 cohorts, 3 rounds in one
     fused scan;
  g  the paged serving engine on the same reduced transformer, against
     the per-token loop engine.

Each training phase runs twice on the chip, with the Pallas server plane
and with the jitted jnp oracle (``--server-plane ref``), and checks that
losses are finite, the params moved, the Pallas program holds a
``tpu_custom_call`` (the oracle's holds none), and the two agree within
``RTOL``/``ATOL``. ``--chips 4`` runs only the pod path with 4 cohorts
over ``engine_mesh`` (client axis 4, ``client_reduce`` auto) against the
same rounds on a one-device mesh.

Lines before the last are set-up information: compile and run seconds,
not metrics. The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed. Without a TPU the script exits non-zero before
any phase.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

#: Pallas vs oracle on one chip: |fused - ref| <= ATOL + RTOL * |ref|,
#: elementwise over the final params and over the per-round losses
RTOL, ATOL = 1e-3, 1e-4
#: four chips vs one: per-round losses within LOSS_RTOL, and the update
#: p - p0 within UPDATE_RTOL of its norm. The reduced transformer keeps
#: bf16 params, so the per-device client programs and the re-associated
#: client-axis sum flip bf16 roundings (a few percent of the update on
#: 4 virtual CPU devices); an all-reduce that dropped one of 4 cohorts
#: would move the update by about a quarter
LOSS_RTOL, UPDATE_RTOL = 1e-3, 0.1
#: marks a Mosaic (Pallas TPU) kernel in compiled HLO
KERNEL_MARK = "tpu_custom_call"
#: the server planes each training phase compares: Pallas, jnp oracle
PLANES = ("fused", "ref")

PAPER = ["--arch", "paper-cnn", "--clients", "50", "--clients-per-round",
         "10", "--p-limited", "0.25", "--algorithm", "ama_fes", "--env",
         "bernoulli", "--rounds", "3", "--eval-every", "1", "--n-train",
         "6000"]
PAPER_PHASES = {
    "a_sync_ama": [],
    "b_async_ama": ["--algorithm", "async_ama", "--max-delay", "15",
                    "--p-delay", "0.3"],
    "c_fedopt": ["--algorithm", "fedopt"],
    "d_q8_uplink": ["--comm-plane", "q8"],
    "d_topk_uplink": ["--comm-plane", "topk"],
    "e_partitioned": ["--p-limited", "0.5", "--client-plane", "partitioned"],
}
POD = ["--arch", "minitron-8b", "--pod", "--reduced", "--rounds", "3"]
SERVE = ["--arch", "minitron-8b", "--reduced", "--prompt-mix",
         "5x2,17x2,40x1", "--tokens", "8", "--max-slots", "4"]


def _setup(msg: str) -> None:
    print(f"setup: {msg}", flush=True)


def _leaves(tree):
    import jax
    import numpy as np
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def _max_excess(got, want, rtol=RTOL, atol=ATOL) -> float:
    """max over elements of |got - want| - (atol + rtol*|want|); <= 0
    means every element is within tolerance."""
    import numpy as np
    return max(float(np.max(np.abs(g - w) - (atol + rtol * np.abs(w))))
               for g, w in zip(_leaves(got), _leaves(want)))


def _check_round_program(runner, plane: str) -> None:
    text = runner.lower_last().compile().as_text()
    if plane != "ref":
        assert KERNEL_MARK in text, "the Pallas server plane did not run"
    else:
        assert KERNEL_MARK not in text, "the oracle run holds a kernel"


def _check_trained(name, params0, params, losses) -> None:
    import numpy as np
    assert np.all(np.isfinite(losses)), f"{name}: losses {losses}"
    moved = max(float(np.max(np.abs(a - b)))
                for a, b in zip(_leaves(params), _leaves(params0)))
    assert moved > 0.0, f"{name}: params did not change"


def _timing(timer) -> str:
    s = timer.summary()
    return "  ".join(f"{k}={v['seconds']:.2f}s/{v['calls']}"
                     for k, v in s.items() if k != "stage")


def _compare(name, runs) -> None:
    import numpy as np
    (p_f, l_f), (p_r, l_r) = (runs[p] for p in PLANES)
    d_p = max(float(np.max(np.abs(a - b)))
              for a, b in zip(_leaves(p_f), _leaves(p_r)))
    d_l = float(np.max(np.abs(l_f - l_r)))
    ex_p, ex_l = _max_excess(p_f, p_r), _max_excess(l_f, l_r)
    print(f"{name}: Pallas vs oracle  max|dparams|={d_p:.3e}  "
          f"max|dloss|={d_l:.3e}  tolerance excess params={ex_p:.3e} "
          f"loss={ex_l:.3e} (<= 0 passes)", flush=True)
    assert ex_p <= 0 and ex_l <= 0, f"{name}: Pallas and oracle disagree"


def paper_phase(name: str, extra: list, seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import train
    runs = {}
    for plane in PLANES:
        args = train.build_parser().parse_args(
            PAPER + extra + ["--server-plane", plane, "--seed", str(seed)])
        fl = train.fl_config(args)
        sim, hist = train.paper_scale(args, fl)
        _setup(f"{name} {plane}: {_timing(sim.timer)}")
        losses = np.asarray(hist.train_loss + hist.test_loss)
        _check_trained(name, sim.model.init(jax.random.PRNGKey(fl.seed)),
                       sim.params, losses)
        assert np.all(np.isfinite(hist.test_acc)), hist.test_acc
        _check_round_program(sim.runner, plane)
        runs[plane] = (sim.params, losses)
    _compare(name, runs)


def pod_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import train
    runs = {}
    for plane in PLANES:
        args = train.build_parser().parse_args(
            POD + ["--cohorts", "2", "--server-plane", plane,
                   "--seed", str(seed)])
        fl = train.fl_config(args)
        state, metrics, runner = train.pod_scale(args, fl)
        _setup(f"f_pod {plane}: {_timing(runner.timer)}")
        losses = np.asarray(metrics["loss"])
        _check_trained("f_pod", runner.model.init(jax.random.PRNGKey(seed)),
                       state["params"], losses)
        _check_round_program(runner, plane)
        runs[plane] = (state["params"], losses)
    _compare("f_pod", runs)


def serve_phase(seed: int) -> None:
    from repro.launch import serve
    tokens = {}
    for engine in ("paged", "loop"):
        args = serve.build_parser().parse_args(SERVE + ["--engine", engine])
        model, params, reqs = serve.build_workload(args, seed)
        eng = serve.build_engine(model, params, args)
        t0 = time.perf_counter()
        results = eng.run(copy.deepcopy(reqs))
        _setup(f"g_serve {engine}: {len(reqs)} requests in "
               f"{time.perf_counter() - t0:.2f}s incl. compile")
        tokens[engine] = {r["id"]: r["tokens"] for r in results}
        assert len(tokens[engine]) == len(reqs)
        for r in reqs:
            got = tokens[engine][r.rid]
            assert len(got) == r.prompt_len + r.max_new, (r.rid, got)
    assert tokens["paged"] == tokens["loop"], "paged and loop tokens differ"
    print(f"g_serve: paged == loop on {len(tokens['loop'])} requests",
          flush=True)


def four_chip_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.launch import train
    from repro.sharding.ctx import constrain_leading
    args = train.build_parser().parse_args(
        POD + ["--cohorts", "4", "--seed", str(seed)])
    fl = train.fl_config(args)
    state4, m4, runner4 = train.pod_scale(args, fl)
    _setup(f"four_chip 4 devices: {_timing(runner4.timer)}")
    mesh = runner4.mesh
    print(f"four_chip: mesh {dict(mesh.shape)}", flush=True)
    assert dict(mesh.shape)["client"] == 4, mesh
    text = runner4.lower_last().compile().as_text()
    n_ar = text.count("all-reduce(")
    print(f"four_chip: compiled round program holds {n_ar} all-reduce ops",
          flush=True)
    assert n_ar > 0, "no all-reduce in the sharded round program"

    # the round step's own constraint on the stacked client params
    with jax.set_mesh(mesh):
        stacked = jax.jit(lambda p: constrain_leading(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (4,) + x.shape), p), "client"))(
                state4["params"])
    leaf = max(jax.tree.leaves(stacked), key=lambda x: x.size)
    print(f"four_chip: stacked client params {leaf.shape} sharding "
          f"{leaf.sharding}", flush=True)
    print(f"four_chip: shard shapes "
          f"{[s.data.shape for s in leaf.addressable_shards]} on devices "
          f"{sorted(d.id for d in leaf.sharding.device_set)}", flush=True)
    assert len(leaf.sharding.device_set) == 4
    assert all(s.data.shape[0] == 1 for s in leaf.addressable_shards)

    one = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
               ("client", "dsub", "model"))
    state1, m1, runner1 = train.pod_scale(args, fl, mesh=one)
    _setup(f"four_chip 1 device: {_timing(runner1.timer)}")
    p0 = _leaves(runner1.model.init(jax.random.PRNGKey(seed)))
    d4 = [a - b for a, b in zip(_leaves(state4["params"]), p0)]
    d1 = [a - b for a, b in zip(_leaves(state1["params"]), p0)]
    norm = np.sqrt(sum(float(np.sum(d * d)) for d in d1))
    diff = np.sqrt(sum(float(np.sum((a - b) ** 2)) for a, b in zip(d4, d1)))
    l4, l1 = np.asarray(m4["loss"]), np.asarray(m1["loss"])
    print(f"four_chip: |update_4 - update_1| / |update_1| = "
          f"{diff / norm:.3e} (<= {UPDATE_RTOL})  losses 4 chips {l4} "
          f"1 device {l1}", flush=True)
    assert np.all(np.isfinite(l4)) and norm > 0
    assert diff <= UPDATE_RTOL * norm, "4-chip and 1-device runs disagree"
    assert _max_excess(l4, l1, rtol=LOSS_RTOL, atol=0.0) <= 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-g on one chip; 4: only the sharded "
                         "pod path on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run this from a "
              "checkout of the repository", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0].platform is "
              f"{platform!r}); nothing ran", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {device['kind']} x{device['count']}", flush=True)

    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    _setup(f"compile cache {enable_compile_cache()}")

    if args.chips == 4:
        phases = {"four_chip": lambda: four_chip_phase(args.seed)}
    else:
        phases = {name: (lambda n=name, e=extra: paper_phase(n, e, args.seed))
                  for name, extra in PAPER_PHASES.items()}
        phases["f_pod"] = lambda: pod_phase(args.seed)
        phases["g_serve"] = lambda: serve_phase(args.seed)
    failed = []
    for name, run in phases.items():
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        _setup(f"phase {name} took {time.perf_counter() - t0:.1f}s "
               f"({'FAILED' if name in failed else 'passed'})")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
