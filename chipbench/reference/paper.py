"""The reference's run of the paper setting: the first rounds of a
federation from the seed's data and initial weights, each followed by
an evaluation on the whole test set.

``fault`` plants one of the faults the benchmark must catch, in the
reference put in the program's place: ``"half_batch"`` trains every
step on the first half of its batch (the mean over the rest), and
``"label"`` alters the labels of the first client's batches where
staging produces them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import federated, schedule


def run(traffic: dict, prog_seed: int, train: dict, test: dict, p0,
        rounds: int, dtype=jnp.float32, fault: str | None = None) -> dict:
    fl = traffic["fl"]
    K, m = fl["num_clients"], fl["clients_per_round"]
    b = fl["local_batch_size"]
    parts = schedule.shards(train["label"], K, prog_seed)
    sizes = np.array([len(p) for p in parts], np.float64)
    steps = fl["local_epochs"] * max(1, min(len(p) for p in parts) // b)
    lim = schedule.limited_set(K, fl["p_limited"], prog_seed)
    n_classes = int(train["label"].max()) + 1
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), p0)
    out = {"loss": [], "eval_loss": [], "eval_acc": []}
    for t in range(rounds):
        chosen = schedule.selected(t, K, m, prog_seed)
        idx = schedule.step_indices(parts, chosen, t, prog_seed, steps, b)
        images, labels = train["image"][idx], train["label"][idx]
        if fault == "half_batch":
            images, labels = images[:, :, :b // 2], labels[:, :, :b // 2]
        elif fault == "label":
            labels = labels.copy()
            labels[0] = (labels[0] + 1) % n_classes
        limited = np.array([int(c) in lim for c in chosen])
        params, loss = federated.fl_round(
            params, t, jnp.asarray(images), jnp.asarray(labels), limited,
            sizes[chosen], fl, dtype)
        acc, eval_loss = federated.evaluate(params, test, dtype)
        out["loss"].append(loss)
        out["eval_loss"].append(eval_loss)
        out["eval_acc"].append(acc)
        out[f"p{t + 1}"] = jax.device_get(params)
    return out
