"""Kernel microbenchmarks: interpret-mode correctness + CPU timing of the
fused oracles (the TPU timing story lives in the roofline; these numbers
prove the kernels run and give a per-call CSV).

The rows go through the SAME entry points the round engine dispatches
(``repro.kernels.server_plane``): the jitted fused oracle for CPU timing
and the interpret-mode Pallas kernels for body validation.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.server_plane import (server_adam_flat, server_async_flat,
                                        server_mix_flat, _ref_adam,
                                        _ref_async, _ref_mix)


def _time(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def _maxerr(got, want) -> float:
    return max(float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                     - jnp.asarray(b, jnp.float32))))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def run(quick=False, smoke=False):
    rows = []
    rng = np.random.RandomState(0)

    # --- server plane: K clients over N flat params, one fused pass ---
    N = 1 << 16 if smoke else (1 << 20 if quick else 1 << 22)
    K, Q = 10, 6
    prev = jnp.asarray(rng.randn(N), jnp.float32)
    stacked = jnp.asarray(rng.randn(K, N).astype(np.float32))
    sizes = jnp.asarray(rng.rand(K) + 0.5, jnp.float32)
    keep = jnp.asarray((rng.rand(K) < 0.7).astype(np.float32))
    delayed = 1.0 - keep                 # async: on-time == kept
    coefs = jnp.asarray([0.1, 2.5e-3, 0.95, 7.0], jnp.float32)
    qsum = jnp.asarray(rng.randn(Q, N).astype(np.float32))
    qgamma = jnp.asarray(rng.rand(Q), jnp.float32)
    delays = jnp.asarray(rng.randint(1, Q, K), jnp.int32)
    tq = jnp.asarray([7, 7 % Q], jnp.int32)
    hyp = jnp.asarray([0.1, 2.5e-3, 0.95, 0.6], jnp.float32)
    m = jnp.asarray(rng.randn(N).astype(np.float32))
    v = jnp.abs(jnp.asarray(rng.randn(N).astype(np.float32)))
    scalars = jnp.asarray([0.9, 0.99, 0.1, 1e-3, 3.0], jnp.float32)

    us = _time(_ref_mix, prev, stacked, sizes, keep, coefs)
    bw = (K + 2) * N * 4 / (us * 1e-6) / 1e9
    rows.append(("server_mix_fused_cpu", us, f"{bw:.1f}GB/s_eff"))
    us = _time(_ref_async, prev, stacked, qsum, qgamma, sizes, delayed,
               delays, tq, hyp)
    rows.append(("server_async_fused_cpu", us, f"K{K}_Q{Q}"))
    us = _time(_ref_adam, prev, stacked, m, v, sizes, keep, scalars)
    rows.append(("server_adam_fused_cpu", us, ""))

    n_val = min(N, 1 << 16)
    sl = lambda x: x[..., :n_val]
    rows.append(("server_mix_interpret_maxerr", _maxerr(
        server_mix_flat(sl(prev), sl(stacked), sizes, keep, coefs,
                        block=8192, interpret=True),
        _ref_mix(sl(prev), sl(stacked), sizes, keep, coefs)), "allclose"))
    rows.append(("server_async_interpret_maxerr", _maxerr(
        server_async_flat(sl(prev), sl(stacked), sl(qsum), qgamma, sizes,
                          delayed, delays, tq, hyp, block=8192,
                          interpret=True),
        _ref_async(sl(prev), sl(stacked), sl(qsum), qgamma, sizes,
                   delayed, delays, tq, hyp)), "allclose"))
    rows.append(("server_adam_interpret_maxerr", _maxerr(
        server_adam_flat(sl(prev), sl(stacked), sl(m), sl(v), sizes, keep,
                         scalars, block=8192, interpret=True),
        _ref_adam(sl(prev), sl(stacked), sl(m), sl(v), sizes, keep,
                  scalars)), "allclose"))

    for name, val, extra in rows:
        print(f"kernel,{name},{val},{extra}")
    for name, val, _ in rows:
        if name.endswith("maxerr"):
            assert val <= 3e-2, (name, val)
    return rows


if __name__ == "__main__":
    import sys
    run(quick="--full" not in sys.argv, smoke="--smoke" in sys.argv)
