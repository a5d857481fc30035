"""Asynchronous AMA under wireless-style delays (paper §IV-B / Fig. 3).

Shows the staleness-weighted ring buffer absorbing delayed updates —
by default across the paper's no-delay / moderate (30%) / severe (70%)
i.i.d. settings, but any registered environment or named scenario works:

    PYTHONPATH=src python examples/async_delays.py
    PYTHONPATH=src python examples/async_delays.py --env gilbert_elliott
    PYTHONPATH=src python examples/async_delays.py --scenario mobility-trace
"""
import argparse

import numpy as np

from repro import env as env_mod
from repro.configs.base import FLConfig
from repro.configs.registry import ARCHS
from repro.core.strategies.async_ama import mixing_weights
from repro.core.simulation import FederatedSimulation
from repro.data.partition import shard_partition
from repro.data.pipeline import build_clients
from repro.data.synth import make_image_classification
from repro.models.api import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="bernoulli", choices=env_mod.names(),
                    help="environment for the delay sweep")
    ap.add_argument("--scenario", default=None,
                    choices=env_mod.scenarios.names(),
                    help="run ONE named scenario instead of the sweep")
    ap.add_argument("--rounds", type=int, default=40)
    args = ap.parse_args()

    fl0 = FLConfig()
    print("staleness-based weights (Eqs. 9-11) at round t=100, three stale "
          "updates with staleness 1, 5, 10:")
    alpha, beta, gammas = mixing_weights(fl0, 100, [1, 5, 10])
    print(f"  alpha={alpha:.4f} beta={beta:.4f} gammas="
          f"{[round(g, 4) for g in gammas]}  (sum={alpha+beta+sum(gammas):.4f})")

    train, test = make_image_classification(n_train=1500, n_test=400, seed=0)
    clients = build_clients(train, shard_partition(train["label"], 20, seed=0))
    model = build_model(ARCHS["paper-cnn"])
    base = FLConfig(num_clients=20, clients_per_round=5, local_epochs=2,
                    local_batch_size=25, lr=0.1, p_limited=0.25,
                    algorithm="ama_fes", seed=0)

    if args.scenario:
        grid = [(args.scenario, env_mod.scenarios.apply(base, args.scenario))]
    elif env_mod.get(args.env).name == "bernoulli":  # aliases included
        # the paper's sweep: delay probability 0 / 30% / 70%, staleness 10
        grid = [(tag, base.with_(env=args.env, p_delay=pd,
                                 max_delay=10 if pd else 0))
                for tag, pd in [("no-delay", 0.0), ("moderate", 0.3),
                                ("severe", 0.7)]]
    else:
        # generic envs own their delay probability; sweep the staleness cap
        grid = [(f"max_delay={md}", base.with_(env=args.env, max_delay=md))
                for md in (0, 5, 15)]

    for tag, fl in grid:
        sim = FederatedSimulation(model, fl, clients, test)
        hist = sim.run(rounds=args.rounds)
        print(f"{tag:15s} [env={fl.env}]: "
              f"accuracy={np.mean(hist.test_acc[-5:]):.3f} "
              f"var={hist.stability_variance(15):.2f}")


if __name__ == "__main__":
    main()
