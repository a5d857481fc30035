"""The paper setting's sampling, restated: which samples each client
holds, which clients a round selects, which are computing-limited, and
which samples each local step trains on.

These are the documented draws of the federation (section V of the
paper, with the program's stated streams): a pathological non-IID split
into two single-class shards per client; m of K clients uniformly
without replacement per round from the round's stream
``RandomState((seed * 1000003 + t) mod 2**32)``; a fixed computing-
limited subset of round(p_limited * K) clients from ``RandomState(seed)``;
and reshuffled-epoch batches from the round's staging stream
``RandomState((seed * 1000003 + t + 0x51ED270) mod 2**32)``, one client
after another in selection order. The reference recomputes them here,
from the seed alone, and so checks the batches staging handed to each
round.
"""
from __future__ import annotations

import numpy as np


def shards(labels: np.ndarray, num_clients: int, seed: int,
           per_client: int = 2) -> list[np.ndarray]:
    """Two single-class shards per client, every sample held once."""
    rng = np.random.RandomState(seed)
    n_classes = int(labels.max()) + 1
    slots = np.arange(num_clients * per_client) % n_classes
    rng.shuffle(slots)
    by_class = []
    for c in range(n_classes):
        idx = np.flatnonzero(labels == c)
        rng.shuffle(idx)
        by_class.append(idx)
    for c in range(n_classes):
        # a class with samples but no slot takes one from a class that
        # has several (never the case with 2K slots over 10 classes)
        if len(by_class[c]) and not np.any(slots == c):
            raise ValueError("fewer slots than classes")
    pieces = {}
    for c in range(n_classes):
        holders = np.flatnonzero(slots == c)
        for h, part in zip(holders, np.array_split(by_class[c],
                                                   len(holders))):
            pieces[int(h)] = part
    out = []
    for client in range(num_clients):
        idx = np.concatenate([pieces[client * per_client + s]
                              for s in range(per_client)])
        rng.shuffle(idx)
        out.append(idx.astype(np.int64))
    return out


def limited_set(num_clients: int, p_limited: float, seed: int) -> set:
    rng = np.random.RandomState(seed)
    k = int(round(p_limited * num_clients))
    return set(rng.choice(num_clients, size=k, replace=False).tolist())


def selected(t: int, num_clients: int, per_round: int,
             seed: int) -> np.ndarray:
    rng = np.random.RandomState((seed * 1_000_003 + t) % 2**32)
    return rng.choice(num_clients, size=per_round, replace=False)


def step_indices(parts, chosen, t: int, seed: int, steps: int,
                 batch: int) -> np.ndarray:
    """(clients, steps, batch) sample indices of round t."""
    rng = np.random.RandomState((seed * 1_000_003 + t + 0x51ED270) % 2**32)
    out = []
    for c in chosen:
        idx = parts[int(c)]
        need = steps * batch
        reps = -(-need // len(idx))
        order = np.concatenate([rng.permutation(idx) for _ in range(reps)])
        out.append(order[:need].reshape(steps, batch))
    return np.stack(out)
