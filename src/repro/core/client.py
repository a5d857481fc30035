"""Client-side local training (paper Alg. 1, lines 11-16).

One engine serves both scales:
  * paper scale — m=10 selected clients vmapped, e local epochs;
  * pod scale  — C cohorts, stacked params sharded over the "client" mesh
    axis; no cross-client collectives inside the local scan (this is the
    defining difference from data-parallel training).

Master weights: where the config keeps them in another dtype than it
computes in (``cfg.param_dtype``, e.g. f32 masters under bf16 compute),
each step differentiates ``model.compute_copy`` of the carry (gradients
in the compute dtype) and applies the SGD step to the masters in f32;
the carry stays in the masters' dtype. Where the two agree the copy is
the params themselves and the program is unchanged.

Algorithm behaviour is injected through the ServerStrategy client hooks
(``local_grad_transform``, ``local_steps``, ``limited_mode``,
``static_local_steps``) — the AMA family masks FES gradients, FedProx
adds the proximal pull (Eq. 4) and runs partial work on limited devices;
this module contains no per-algorithm branching.

Three client-plane programs (``fl.client_plane`` / ``fl.fes_static``):
  * ``make_local_train`` — the MASKED plane: one program for every
    cohort, ``limited`` a traced per-cohort bool. Limited cohorts pay
    the full body backward and mask/freeze it — the bit-identity
    reference for mixed cohorts.
  * ``make_limited_local_train`` — the limited-group program of the
    PARTITIONED plane: classifier-only differentiation (the body
    backward is never traced — the paper's Eq. 3 computation reduction
    for real) or a statically truncated full-gradient scan (FedProx
    partial work), per the strategy's ``limited_mode``.
  * ``make_fes_local_train`` — STATIC mode: every cohort limited.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core import fes as fes_lib
from repro.core import strategies


def _sgd(params, grads, lr: float):
    """The shared local SGD update (f32 accumulate, params' dtype out) —
    bit-identical to the masked plane's active branch."""
    return jax.tree.map(
        lambda p, gi: (p.astype(jnp.float32)
                       - lr * gi.astype(jnp.float32)).astype(p.dtype),
        params, grads)


def make_local_train(model, fl: FLConfig, strategy=None):
    """Returns local_train(global_params, batches, limited) ->
    (client_params (C, ...), mean_loss (C,)).

    batches: pytree with leading (C, steps, batch, ...) axes.
    limited: (C,) bool — FES-limited cohorts (dynamic mask mode).
    """
    strategy = strategy or strategies.resolve(fl)
    grad_fn = jax.value_and_grad(model.loss)

    def one_client(params0, global_params, batches, limited):
        mask = model.fes_mask(params0)
        n_steps = jax.tree.leaves(batches)[0].shape[0]
        n_active = strategy.local_steps(n_steps, limited)

        def step(carry, mb):
            params, i = carry
            loss, g = grad_fn(model.compute_copy(params), mb)
            g = strategy.local_grad_transform(g, params, global_params,
                                              mask, limited)
            active = i < n_active
            new_params = jax.tree.map(
                lambda p, gi: jnp.where(
                    active,
                    (p.astype(jnp.float32)
                     - fl.lr * gi.astype(jnp.float32)), p.astype(jnp.float32)
                ).astype(p.dtype),
                params, g)
            return (new_params, i + 1), loss

        (params, _), losses = jax.lax.scan(
            step, (params0, jnp.int32(0)), batches)
        # losses past the strategy's local_steps cutoff are computed at
        # FROZEN params (partial work keeps scanning but stops updating);
        # averaging them in would bias mean_loss toward the stale value,
        # so the mean covers active steps only
        active = jnp.arange(n_steps) < n_active
        mean_loss = (jnp.sum(losses * active.astype(losses.dtype))
                     / jnp.maximum(n_active, 1).astype(losses.dtype))
        return params, mean_loss

    def local_train(global_params, batches, limited):
        return jax.vmap(one_client, in_axes=(None, None, 0, 0))(
            global_params, global_params, batches, limited)

    return local_train


def make_limited_local_train(model, fl: FLConfig, strategy=None):
    """The limited-cohort program of the PARTITIONED client plane.

    Returns local_train(global_params, batches) -> (client_params
    (L, ...), mean_loss (L,)) for a group of cohorts that are ALL
    computing-limited. Generalizes ``make_fes_local_train`` through the
    strategy's client hooks:

      * ``limited_mode == "classifier"`` (AMA-FES): classifier-only
        differentiation — the body backward is never traced, so limited
        devices pay forward + classifier backward only (Eq. 3), instead
        of the masked plane's computed-then-zeroed full backward;
      * ``limited_mode == "full"`` (FedProx, base): the same gradients
        an unlimited cohort takes, over a STATICALLY truncated scan of
        ``static_local_steps`` steps — partial work as a shorter scan,
        not computed-and-discarded gradients.

    Cohorts whose params/losses the caller discards (padding slots of a
    chunk-static partition) are the caller's concern; every row here is
    trained as a real limited cohort.
    """
    strategy = strategy or strategies.resolve(fl)

    if strategy.limited_mode == "classifier":
        grad_fn = jax.value_and_grad(fes_lib.fes_loss_fn(model))

        def one_client(params0, global_params, batches):
            n_steps = jax.tree.leaves(batches)[0].shape[0]
            n_active = min(strategy.static_local_steps(n_steps), n_steps)
            batches = jax.tree.map(lambda x: x[:n_active], batches)
            clf0, body = fes_lib.split_params(params0)
            clf_mask, _ = fes_lib.split_params(model.fes_mask(params0))
            clf_global, _ = fes_lib.split_params(global_params)
            body_c = model.compute_copy(body)

            def step(clf, mb):
                loss, g = grad_fn(model.compute_copy(clf), body_c, mb)
                g = strategy.local_grad_transform(g, clf, clf_global,
                                                  clf_mask, True)
                return _sgd(clf, g, fl.lr), loss

            clf, losses = jax.lax.scan(step, clf0, batches)
            return fes_lib.merge_params(clf, body), jnp.mean(losses)

    else:  # "full": unlimited gradients over the truncated step budget
        grad_fn = jax.value_and_grad(model.loss)

        def one_client(params0, global_params, batches):
            mask = model.fes_mask(params0)
            n_steps = jax.tree.leaves(batches)[0].shape[0]
            n_active = min(strategy.static_local_steps(n_steps), n_steps)
            batches = jax.tree.map(lambda x: x[:n_active], batches)

            def step(params, mb):
                loss, g = grad_fn(model.compute_copy(params), mb)
                g = strategy.local_grad_transform(g, params, global_params,
                                                  mask, True)
                return _sgd(params, g, fl.lr), loss

            params, losses = jax.lax.scan(step, params0, batches)
            return params, jnp.mean(losses)

    def local_train(global_params, batches):
        return jax.vmap(one_client, in_axes=(None, None, 0))(
            global_params, global_params, batches)

    return local_train


def make_partitioned_local_train(model, fl: FLConfig, strategy=None):
    """The PARTITIONED mixed-cohort client plane.

    Returns local_train(global_params, batches, sched) -> (client_params
    (C, ...), mean_loss (C,)) — the same contract as the masked plane,
    but each round's cohorts are grouped by limited-ness (the host-side
    ``data.pipeline.partition_plan`` arrays riding in ``sched``) and
    dispatched as TWO vmapped programs: the full/masked program over the
    ``part_full_idx`` group and the classifier-only / truncated program
    (``make_limited_local_train``) over the ``part_lim_idx`` group. The
    stacked outputs are scattered back into cohort-slot order, so the
    fused server update downstream is oblivious to the split.

    Group widths are STATIC per compiled program (they come in as array
    shapes): per chunk, the limited program takes the chunk-minimum
    limited count and overflow limited cohorts run the masked program
    (still correct — just unreduced); a 1-round chunk therefore gets the
    exact per-round split.
    """
    strategy = strategy or strategies.resolve(fl)
    full_train = make_local_train(model, fl, strategy)
    lim_train = make_limited_local_train(model, fl, strategy)

    def local_train(global_params, batches, sched):
        full_idx = sched["part_full_idx"]
        lim_idx = sched["part_lim_idx"]
        src_row = sched["part_src_row"]
        from_lim = sched["part_from_lim"]
        U, L = full_idx.shape[0], lim_idx.shape[0]
        if U:
            f_params, f_loss = full_train(
                global_params,
                jax.tree.map(lambda x: x[full_idx], batches),
                sched["limited"][full_idx])
        if L:
            l_params, l_loss = lim_train(
                global_params,
                jax.tree.map(lambda x: x[lim_idx], batches))
        if not L:
            return (jax.tree.map(lambda f: f[src_row], f_params),
                    f_loss[src_row])
        if not U:
            return (jax.tree.map(lambda l: l[src_row], l_params),
                    l_loss[src_row])

        def scatter(f, l):
            fr = f[jnp.minimum(src_row, U - 1)]
            lr = l[jnp.minimum(src_row, L - 1)]
            sel = from_lim.reshape(from_lim.shape + (1,) * (fr.ndim - 1))
            return jnp.where(sel, lr, fr)

        return (jax.tree.map(scatter, f_params, l_params),
                scatter(f_loss, l_loss))

    return local_train


def make_fes_local_train(model, fl: FLConfig):
    """STATIC FES local training: classifier-only differentiation.

    The body backward is never traced — this is the lowering used to show
    the FES computation reduction in the dry-run/roofline.
    """
    loss_fn = fes_lib.fes_loss_fn(model)
    grad_fn = jax.value_and_grad(loss_fn)

    def one_client(params0, batches):
        clf0, body = fes_lib.split_params(params0)
        body_c = model.compute_copy(body)

        def step(clf, mb):
            loss, g = grad_fn(model.compute_copy(clf), body_c, mb)
            return _sgd(clf, g, fl.lr), loss

        clf, losses = jax.lax.scan(step, clf0, batches)
        return fes_lib.merge_params(clf, body), jnp.mean(losses)

    def local_train(global_params, batches, limited=None):
        del limited
        return jax.vmap(one_client, in_axes=(None, 0))(global_params, batches)

    return local_train
