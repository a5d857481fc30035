"""jit'd wrappers: the public kernel API used by the rest of the framework.

``ama_mix_tree`` / ``ama_mix_pairwise`` (the legacy per-leaf mix) run the
Pallas kernel compiled on a TPU and in the Pallas interpreter elsewhere.
The fused server plane picks its path itself (``kernels.server_plane``):
the kernel on a TPU, the jitted jnp oracle off it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ama_mix import ama_mix_flat
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.server_plane import (server_adam_flat, server_adam_tree,
                                        server_async_flat, server_async_tree,
                                        server_mix_flat, server_mix_tree)

__all__ = ["ama_mix_flat", "flash_attention", "rwkv6_scan",
           "ama_mix_tree", "ama_mix_pairwise",
           "server_mix_flat", "server_async_flat", "server_adam_flat",
           "server_mix_tree", "server_async_tree", "server_adam_tree"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ama_mix_tree(prev_tree, stacked_tree, alpha, weights, *,
                 interpret: bool | None = None):
    """AMA aggregation over whole param pytrees through the fused kernel.

    prev_tree leaves (..., ); stacked_tree leaves (K, ...).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret

    def one(p, s):
        K = s.shape[0]
        flat_p = p.reshape(-1)
        flat_s = s.reshape(K, -1)
        out = ama_mix_flat(flat_p, flat_s, alpha, weights,
                           interpret=interpret)
        return out.reshape(p.shape)

    return jax.tree.map(one, prev_tree, stacked_tree)


def ama_mix_pairwise(prev_tree, agg_tree, alpha, *, interpret=None):
    """alpha*prev + (1-alpha)*agg via the same kernel (K=1)."""
    interpret = (not _on_tpu()) if interpret is None else interpret

    def one(p, g):
        flat_p = p.reshape(-1)
        flat_s = g.reshape(1, -1)
        w = (1.0 - jnp.asarray(alpha, jnp.float32)).reshape(1)
        return ama_mix_flat(flat_p, flat_s, alpha, w,
                            interpret=interpret).reshape(p.shape)

    return jax.tree.map(one, prev_tree, agg_tree)
