"""Mesh-context-aware sharding constraints.

Model code is mesh-agnostic; ``constrain`` applies a
with_sharding_constraint only when a mesh with the named axes is active
and every named dim divides its axis — otherwise it is a no-op (CPU
tests, reduced configs). On a sharded client axis the server's state
lives split over it (``server_spec``): the round gathers it whole for
local training and reduce-scatters the clients' weighted sum back onto
the shards. The active mesh is the one entered with
``jax.set_mesh`` (the engine and the dry-run do), read through the
public ``jax.sharding.get_abstract_mesh``; a bare ``with mesh:`` is not
seen."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _active_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_axis_names() -> tuple:
    m = _active_mesh()
    return tuple(m.axis_names) if m is not None else ()


def constrain(x, *axes):
    """axes: one entry per dim of x — mesh axis name or None."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    fixed = []
    for ax, dim in zip(axes, x.shape):
        if ax is None or ax not in mesh.axis_names:
            fixed.append(None)
            continue
        size = mesh.shape[ax]
        fixed.append(ax if size and dim % size == 0 else None)
    if not any(fixed):
        return x
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def axis_size(name: str) -> int:
    """Size of a mesh axis in the ACTIVE mesh context (1 when no mesh is
    active or the axis doesn't exist) — how the round engine decides at
    trace time whether the client axis is actually distributed."""
    mesh = _active_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return int(mesh.shape[name])


def server_spec(shape, n: int, lead: int = 0) -> P:
    """Where a leaf of the server's state lives over ``n`` client
    shards: split on its largest dim that ``n`` divides (the first such
    on a tie), past ``lead`` leading dims that stay whole; replicated
    when ``n`` is 1 or no dim divides."""
    axes = [None] * len(shape)
    dims = [i for i in range(lead, len(shape)) if shape[i] % n == 0]
    if n > 1 and dims:
        axes[max(dims, key=lambda i: (shape[i], -i))] = "client"
    return P(*axes)


def server_shardings(tree, mesh):
    """``NamedSharding`` per leaf of the server's state on ``mesh``."""
    from jax.sharding import NamedSharding
    n = int(mesh.shape["client"]) if "client" in mesh.axis_names else 1
    return jax.tree.map(
        lambda x: NamedSharding(mesh, server_spec(x.shape, n)), tree)


def scatter_server(tree, lead: int = 0):
    """Constrain each leaf to its shard of the server state
    (``server_spec``) on the active mesh's client axis; a no-op where
    that axis is 1 wide or no mesh is active."""
    n = axis_size("client")
    if n == 1:
        return tree
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(
            x, server_spec(x.shape, n, lead)) if getattr(x, "ndim", 0)
        else x, tree)


def gather_server(tree):
    """The whole server state on every client shard (an all-gather of
    the ``scatter_server`` shards); a no-op off a sharded client axis."""
    if axis_size("client") == 1:
        return tree
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, P())
        if getattr(x, "ndim", 0) else x, tree)


def reduce_leading(tree, weights):
    """Weighted sum over every leaf's LEADING (client) axis, f32.

    weights (C,) -> leaf (C, ...) contracts to (...); weights (C, R) ->
    (R, ...) (R simultaneous reductions — e.g. the async plane's on-time
    aggregate + Q ring-buffer enqueue slots in one contraction). The
    input is constrained onto the mesh's "client" axis first, so on a
    sharded mesh XLA lowers this as a LOCAL partial sum followed by one
    N-byte (or R x N) collective — the per-round collective moves the
    model size, not cohorts x model size. The sum lands split over the
    client axis (``scatter_server``), so that collective is a
    reduce-scatter and each shard keeps its part of the server state.
    The contraction runs at HIGHEST precision: a TPU's default f32 dot
    takes one bf16 pass, which would round the aggregated model to 8
    mantissa bits.
    """
    w = weights.astype(jnp.float32)
    eq = "c...,cr->r..." if w.ndim == 2 else "c...,c->..."

    def red(x):
        if not getattr(x, "ndim", 0):
            return x
        xc = constrain(x, "client", *([None] * (x.ndim - 1)))
        y = jnp.einsum(eq, xc.astype(jnp.float32), w,
                       precision=jax.lax.Precision.HIGHEST)
        return scatter_server(y, lead=w.ndim - 1)

    # the span the trace reads the round's cross-chip reduction by
    with jax.named_scope("client_reduce"):
        return jax.tree.map(red, tree)


def constrain_leading(tree, axis: str):
    """Constrain every leaf of a pytree on its LEADING dim only.

    The engine uses this on the stacked client axis: batches
    (C, steps, b, ...) and stacked client params (C, ...) shard over the
    FL mesh's "client" axis while the trailing dims stay unconstrained
    (FSDP/TP constraints belong to the model code). No-op leaf-wise when
    no mesh is active or the axis doesn't divide (CPU tests)."""
    return jax.tree.map(
        lambda x: constrain(x, axis, *([None] * (x.ndim - 1)))
        if getattr(x, "ndim", 0) else x, tree)
