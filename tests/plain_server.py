"""Plain per-leaf statements of the five server rules, in float64 numpy.

The reference the strategy tests hold the server planes to. Each rule is
written from the paper's equations, one leaf at a time, with none of the
code under test: no fused coefficient vector, no ring buffer, no
pre-reduction.

  * ama (Eq. 5): alpha_t * prev + (1 - alpha_t) * sum_i w_i x_i over
    the on-time clients, alpha_t = min(alpha0 + eta * t, alpha_cap);
  * fedavg: the weighted average of the clients that are on time AND
    not computing-limited;
  * fedprox: the weighted average of the on-time clients;
  * async_ama (Eqs. 6-11): every delayed update is kept as an event and
    mixed in at its arrival round with its staleness weight;
  * fedopt: one server-Adam step on the pseudo-gradient
    (on-time average - prev).

Weights are w_i = |d_i| / sum_j |d_j| over the kept clients; when nobody
is kept the previous model stands in for the average.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: the schedules the server paths are checked under
SCHEDULES = ("all_on_time", "mixed", "none_on_time")


def schedule(rng, C, kind, max_delay=0):
    """One round's schedule of ``C`` clients: every client on time, a
    mix of limited and delayed clients, or every client delayed."""
    delayed = {"all_on_time": np.zeros(C, bool), "mixed": rng.rand(C) < 0.5,
               "none_on_time": np.ones(C, bool)}[kind]
    delays = np.where(delayed, rng.randint(1, max(max_delay, 1) + 1, C), 1)
    return {"limited": jnp.asarray(rng.rand(C) < 0.5),
            "delayed": jnp.asarray(delayed),
            "delays": jnp.asarray(delays.astype(np.int32)),
            "data_sizes": jnp.asarray(rng.rand(C) + 0.5, jnp.float32)}


def as_f64(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float64), tree)


def alpha_t(fl, t) -> float:
    return min(fl.alpha0 + fl.eta * t, fl.alpha_cap)


def average(prev, clients, sizes, keep):
    """sum_i w_i x_i over the kept clients, or ``prev`` if none is."""
    w = np.asarray(sizes, np.float64) * np.asarray(keep, np.float64)
    if w.sum() == 0:
        return prev
    w = w / w.sum()
    return jax.tree.map(lambda x: np.tensordot(w, x, axes=1), clients)


def mix(prev, clients, sizes, keep, alpha):
    agg = average(prev, clients, sizes, keep)
    return jax.tree.map(lambda p, a: alpha * p + (1.0 - alpha) * a,
                        prev, agg)


def sigmoid(x: float) -> float:
    return 1.0 / (1.0 + math.exp(-x))


class PlainServer:
    """One rule's server update, round after round: ``step(t, prev,
    clients, sched)`` returns the new global model (float64 leaves) and
    keeps the rule's own state (pending events, Adam moments)."""

    def __init__(self, fl):
        self.fl = fl
        self.rule = "ama" if fl.algorithm == "ama_fes" else fl.algorithm
        if self.rule == "ama" and fl.max_delay > 0:
            self.rule = "async_ama"
        self.pending = []          # (arrival round, sent round, update)
        self.m = self.v = None
        self.n_steps = 0

    def step(self, t, prev, clients, sched):
        prev, clients = as_f64(prev), as_f64(clients)
        s = {k: np.asarray(v) for k, v in sched.items()}
        on_time = ~s["delayed"]
        sizes = s["data_sizes"]
        if self.rule == "ama":
            return mix(prev, clients, sizes, on_time, alpha_t(self.fl, t))
        if self.rule == "fedavg":
            return mix(prev, clients, sizes, on_time & ~s["limited"], 0.0)
        if self.rule == "fedprox":
            return mix(prev, clients, sizes, on_time, 0.0)
        if self.rule == "async_ama":
            return self._async(t, prev, clients, s)
        assert self.rule == "fedopt", self.rule
        return self._adam(prev, clients, sizes, on_time)

    def _async(self, t, prev, clients, s):
        fl = self.fl
        for i in np.flatnonzero(s["delayed"]):
            self.pending.append((t + int(s["delays"][i]), t,
                                 jax.tree.map(lambda x, i=i: x[i], clients)))
        arrived = [(n, x) for at, n, x in self.pending if at == t]
        self.pending = [e for e in self.pending if e[0] != t]
        A = alpha_t(fl, t)
        alpha_un = 1.0 - sigmoid(1.0)                         # Eq. 9
        g_un = [fl.staleness_b * (1.0 - sigmoid(t - n)) for n, _ in arrived]
        denom = alpha_un + sum(g_un)
        alpha = alpha_un / denom * A                          # Eq. 10
        gammas = [g / denom * A for g in g_un]                # Eq. 11
        agg = average(prev, clients, s["data_sizes"], ~s["delayed"])
        out = jax.tree.map(lambda p, a: alpha * p + (1.0 - A) * a, prev, agg)
        for g, (_, x) in zip(gammas, arrived):
            out = jax.tree.map(lambda o, xi, g=g: o + g * xi, out, x)
        return out

    def _adam(self, prev, clients, sizes, keep):
        fl = self.fl
        if self.m is None:
            self.m = jax.tree.map(np.zeros_like, prev)
            self.v = jax.tree.map(np.zeros_like, prev)
        self.n_steps += 1
        agg = average(prev, clients, sizes, keep)
        d = jax.tree.map(lambda a, p: a - p, agg, prev)
        b1, b2 = fl.server_b1, fl.server_b2
        self.m = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, self.m, d)
        self.v = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x,
                              self.v, d)
        bc1, bc2 = 1 - b1 ** self.n_steps, 1 - b2 ** self.n_steps
        return jax.tree.map(
            lambda p, m, v: p + fl.server_lr * (m / bc1)
            / (np.sqrt(v / bc2) + fl.server_tau), prev, self.m, self.v)
