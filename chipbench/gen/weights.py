"""Seeded weights, made on the device in one jitted call.

``specs`` is a nested dict whose leaves are ``(shape, init, scale,
dtype)`` tuples; ``init`` is ``"normal"`` (scale = standard deviation),
``"uniform"`` (on [-scale, scale]) or ``"zeros"``. The reference models
under ``chipbench/reference`` state their specs; the program and the
reference are both handed the arrays made here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 4 and isinstance(x[1], str)


def make(specs: dict, seed: int):
    """Weights for ``specs`` from ``seed`` (any non-negative int)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, init, scale, dtype) in zip(keys, leaves):
            if init == "normal":
                x = scale * jax.random.normal(k, shape, jnp.float32)
            elif init == "uniform":
                x = jax.random.uniform(k, shape, jnp.float32, -scale, scale)
            elif init == "zeros":
                x = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError(f"unknown init {init!r}")
            out.append(x.astype(dtype))
        return treedef.unflatten(out)

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), (seed >> 32) % 2**32)
    return jax.jit(build)(key)
