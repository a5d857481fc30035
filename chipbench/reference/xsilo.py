"""Plain reference of the cross-silo cell's federated rounds, restated
from the seed alone: synchronous AMA-FES (paper Algorithm 1 with Eqs.
2, 3 and 5) over C silos that all train every round.

* Tokens: ``chipbench.gen.tokens`` from ``(seed, round, silo)``; silo
  ``selected[c]`` fills cohort slot ``c``.
* Schedule: the round's order of silos and the fixed computing-limited
  subset of round(p_limited * C) silos, from the program's documented
  streams (``chipbench.reference.schedule``).
* Local training: each silo runs ``local_steps`` SGD steps from the
  global weights, one batch of sequences a step
  (``chipbench.reference.lm.sgd_step``); a limited silo's feature
  extractor gradient is masked to zero (Eq. 3).
* Eq. 5: omega_t = alpha_t omega_{t-1} + (1 - alpha_t) sum_i w_i omega_ti
  with alpha_t = min(alpha0 + eta t, alpha_cap) and w_i equal (every
  silo's |D_i| is 1 in this cell).
* A round's loss: the mean over silos of each silo's mean step loss.

The silos of a round are one stacked array split over ``devices`` (one
silo a chip on four chips); the global weights stay on the host between
rounds, so a device never holds them beside a silo's training. With
``group`` set, the silos train ``group`` at a time (one chip can hold
one silo's f32 step, not four) and the host sums their updates in slot
order, one f32 multiply-add a silo, where the stacked run sums them on
the devices: the two differ by the order of a four-term f32 sum.

``fault`` plants one of the faults the comparison must catch:
``"drop_silo"`` leaves slot 0's update out of the mix (its weight goes
to the others), ``"shift_targets"`` trains each position on the token
two ahead, ``"bf16_carry"`` rounds the stepped weights to bfloat16
every step, ``"half_batch"`` trains each step on the first half of its
batch's sequences. ``dtype=bfloat16`` is the control: weights and
arithmetic one precision down.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.gen import tokens as gen_tokens
from chipbench.reference import lm, schedule

FAULTS = ("drop_silo", "shift_targets", "bf16_carry", "half_batch")


def round_plan(traffic: dict, prog_seed: int, t: int):
    """(silo of each cohort slot, limited flag of each slot) in round t."""
    fl = traffic["fl"]
    C = fl["cohorts"]
    chosen = schedule.selected(t, C, C, prog_seed)
    lim = schedule.limited_set(C, fl["p_limited"], prog_seed)
    return chosen, np.array([int(s) in lim for s in chosen])


def round_batch(traffic: dict, seed: int, t: int, chosen) -> np.ndarray:
    """(C, steps, batch, seq) int32 tokens of round t, slot order."""
    fl, g = traffic["fl"], traffic["generator"]
    return gen_tokens.round_tokens(
        seed, t, chosen, (fl["local_steps"], g["batch"], g["seq"]),
        g["vocab"], g["zipf_exponent"])


def _cfg_items(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"),
                   donate_argnums=(0,))
def _silo_steps(stacked, toks, limited, lr, shift, bf16_carry, seqs, *,
                cfg_items, dtype):
    """One local step of every silo: stacked (C, ...), toks (C, b, S).
    The planted faults are arguments, so that they run the sound
    reference's program."""
    def one(p, tk, lim):
        return lm.sgd_step(p, dict(cfg_items), tk, lr, lim, dtype=dtype,
                           shift=shift, bf16_carry=bf16_carry, seqs=seqs)
    return jax.vmap(one)(stacked, toks, limited)


@functools.partial(jax.jit, donate_argnums=(0,))
def _mix(prev, stacked, w, alpha):
    def leaf(p, s):
        agg = jnp.einsum("c...,c->...", s.astype(jnp.float32), w,
                         precision=jax.lax.Precision.HIGHEST)
        return (alpha * p.astype(jnp.float32)
                + (1.0 - alpha) * agg).astype(p.dtype)
    return jax.tree.map(leaf, prev, stacked)


def _stacked(glob, C: int, devices):
    """The host weights ``glob`` as C stacked silos, split over
    ``devices`` (C/len(devices) silos a device)."""
    n = len(devices)
    sh = NamedSharding(Mesh(np.asarray(devices), ("silo",)), P("silo"))

    def leaf(a):
        part = np.broadcast_to(a[None], (C // n,) + a.shape)
        return jax.make_array_from_single_device_arrays(
            (C,) + a.shape, sh, [jax.device_put(part, d) for d in devices])
    return jax.tree.map(leaf, glob), sh


def _accumulate(agg, silos, w):
    """agg += sum_i w[i] silos[i] on the host in f32, one silo at a time
    in slot order."""
    def leaf(a, s):
        for i in range(s.shape[0]):
            a += np.float32(w[i]) * np.asarray(s[i], np.float32)
    jax.tree.map(leaf, agg, silos)


def run(traffic: dict, cfg: dict, seed: int, prog_seed: int, p0,
        rounds: int, devices, dtype=jnp.float32,
        fault: str | None = None, group: int | None = None) -> dict:
    """The first ``rounds`` rounds from host weights ``p0``: {"loss":
    [per round], "p1", ... : host weights after each round}. ``group``:
    the silos trained at a time (all by default)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    fl, g = traffic["fl"], traffic["generator"]
    C, lr = fl["cohorts"], float(fl["lr"])
    group = C if group is None else group
    if C % group or group % len(devices):
        raise ValueError(f"{C} silos do not split into groups of {group} "
                         f"over {len(devices)} devices")
    kw = dict(cfg_items=_cfg_items(cfg), dtype=jnp.dtype(dtype))
    shift = jnp.int32(2 if fault == "shift_targets" else 1)
    carry = jnp.bool_(fault == "bf16_carry")
    seqs = jnp.int32(g["batch"] // 2 if fault == "half_batch"
                     else g["batch"])
    out = {"loss": []}
    glob = jax.tree.map(lambda a: np.asarray(a, dtype), p0)
    for t in range(rounds):
        chosen, limited = round_plan(traffic, prog_seed, t)
        batch = round_batch(traffic, seed, t, chosen)
        w = np.full(C, 1.0 / C, np.float32)
        if fault == "drop_silo":
            w[0], w[1:] = 0.0, 1.0 / (C - 1)
        alpha = min(fl["alpha0"] + fl["eta"] * t, fl["alpha_cap"])
        losses = []
        agg = (None if group == C else jax.tree.map(
            lambda a: np.zeros(a.shape, np.float32), glob))
        for c0 in range(0, C, group):
            stacked, sh = _stacked(glob, group, devices)
            toks = jax.device_put(batch[c0:c0 + group], sh)
            lim = jax.device_put(limited[c0:c0 + group], sh)
            steps = []
            for s in range(fl["local_steps"]):
                stacked, loss = _silo_steps(stacked, toks[:, s], lim,
                                            jnp.float32(lr), shift, carry,
                                            seqs, **kw)
                steps.append(np.asarray(loss))
            losses.append(np.stack(steps))
            if group == C:
                mixed = _mix(jax.device_put(glob,
                                            NamedSharding(sh.mesh, P())),
                             stacked, jnp.asarray(w), jnp.float32(alpha))
                jax.tree.map(lambda a: a.delete(), stacked)
                glob = jax.device_get(mixed)
                jax.tree.map(lambda a: a.delete(), mixed)
            else:
                host = jax.device_get(stacked)
                jax.tree.map(lambda a: a.delete(), stacked)
                _accumulate(agg, host, w[c0:c0 + group])
                del host
        if agg is not None:
            a = np.float32(alpha)
            glob = jax.tree.map(
                lambda p, s: (a * np.asarray(p, np.float32)
                              + (np.float32(1) - a) * s).astype(p.dtype),
                glob, agg)
            del agg
        per_silo = np.mean(np.concatenate(losses, axis=1), axis=0)
        out["loss"].append(float(np.mean(per_silo)))
        out[f"p{t + 1}"] = glob
    return out
