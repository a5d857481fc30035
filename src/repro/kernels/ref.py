"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth),
and plain attention, the reference of the model's chunked attention.

Each ``server_*_math`` oracle is two shared halves that the fused
server-plane kernels (``kernels/server_plane.py``) call too:

  * ``server_*_coefs`` — the O(K·Q) scalar part (participation and
    staleness weights, the alpha schedule, Adam's bias corrections),
    packed into one flat f32 vector. The kernel wrappers compute it in
    XLA outside the ``pallas_call`` and hand it to the kernel in SMEM,
    so no scalar transcendental (``pow``, ``sigmoid``) is lowered by
    Mosaic;
  * ``*_apply`` — the O(N) elementwise pass over the parameter axis.
    It only indexes its row operands (``rows[k]``) and its coefficient
    vector (``c[i]``), so the kernel passes its block Refs and SMEM Ref
    where the oracle passes whole arrays.

Elementwise math and the sequential client-axis accumulation are
therefore the identical op sequence in both; the interpret-mode kernels
match these oracles to within 1-2 ulp (XLA's multiply-add contraction is
shape-dependent, so strict bit-equality across different blockings is
not guaranteed — the engine's scan==loop bit-identity instead comes from
both paths running the SAME program). Compiled TPU mode is allclose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# fused server plane (one HBM pass per round): shared kernel/oracle math
# ---------------------------------------------------------------------------

def _norm_weights(sizes, keep):
    """w_i = |d_i|*keep_i / sum_j |d_j|*keep_j (the FedAvg convention);
    ``keep`` is a {0,1} f32 mask. Returns (w, tot)."""
    w = sizes.astype(jnp.float32) * keep.astype(jnp.float32)
    tot = jnp.sum(w)
    return w / jnp.maximum(tot, 1e-9), tot


def _mix_schedule(coefs):
    """alpha_t = min(alpha0 + eta*t, cap) from coefs = [alpha0, eta,
    alpha_cap, t]; returns (alpha, beta = 1 - alpha)."""
    alpha = jnp.minimum(coefs[0] + coefs[1] * coefs[3], coefs[2])
    return alpha, 1.0 - alpha


def mix_apply(prev, rows, c):
    """out = prev*c[0] + sum_k rows[k]*c[k+1], f32 accumulation.

    The sequential multiply-add chain over the static client axis: XLA
    fuses it into ONE pass reading each element once (measurably faster
    than an einsum contraction on CPU), and the per-element op order is
    independent of the blocking, so the kernel tiles and the
    whole-array oracle agree to 1-2 ulp. ``rows`` is (K, ...) and may
    be int8/bf16/f32 (upcast per row)."""
    acc = prev.astype(jnp.float32) * c[0]
    for k in range(rows.shape[0]):
        acc = acc + rows[k].astype(jnp.float32) * c[k + 1]
    return acc.astype(prev.dtype)


def server_mix_coefs(sizes, keep, coefs):
    """(K+1,) f32 = [a_eff, beta*w_0, ..., beta*w_{K-1}] for the sync
    plane. When nobody is kept (tot == 0) the whole beta budget reverts
    to the previous model (a_eff = alpha + beta)."""
    alpha, beta = _mix_schedule(coefs)
    w, tot = _norm_weights(sizes, keep)
    a_eff = jnp.where(tot > 0, alpha, alpha + beta)
    return jnp.concatenate([a_eff[None], beta * w])


def server_mix_math(prev, stacked, sizes, keep, coefs):
    """The sync server plane: staleness/participation weights + weighted
    client accumulation + AMA mix, one pass over the parameter axis.

    prev: (n,); stacked: (K, n); sizes/keep: (K,) f32;
    coefs: (4,) f32 = [alpha0, eta, alpha_cap, t]. alpha_t = min(alpha0 +
    eta*t, cap) computed here, so fedavg/fedprox pass zeros for an
    alpha=0 plain weighted average.
    """
    return mix_apply(prev, stacked, server_mix_coefs(sizes, keep, coefs))


def server_mix_delta_coefs(rowscale, sizes, keep, coefs):
    """(K+1,) f32 = [a_eff + beta*sum_k w_k, beta*w_k*rowscale_k ...]
    for the compressed-delta sync plane (see ``server_mix_delta_math``)."""
    alpha, beta = _mix_schedule(coefs)
    w, tot = _norm_weights(sizes, keep)
    a_eff = jnp.where(tot > 0, alpha, 1.0)
    return jnp.concatenate([(a_eff + beta * jnp.sum(w))[None],
                            beta * w * rowscale.astype(jnp.float32)])


def server_mix_delta_math(prev, dstacked, rowscale, sizes, keep, coefs):
    """The sync server plane consuming COMPRESSED CLIENT DELTAS: row k of
    ``dstacked`` is client k's quantized delta d_k = x_k - prev (int8 or
    bf16; ``rowscale[k]`` de-quantizes it), and the dequantize-accumulate
    happens inside the one pass:

        out = prev * (a_eff + beta * sum_k w_k)
              + sum_k (beta * w_k * rowscale[k]) * d_k

    — algebraically ``server_mix_math`` with x_k = prev + s_k d_k
    substituted (sum_k w_k is 1 when anybody is kept, 0 otherwise, so
    the tot == 0 round reverts to the previous model exactly as the
    dense plane does).

    prev: (n,); dstacked: (K, n) int8/bf16/f32; rowscale/sizes/keep:
    (K,) f32; coefs: (4,) f32 = [alpha0, eta, alpha_cap, t].
    """
    return mix_apply(prev, dstacked,
                     server_mix_delta_coefs(rowscale, sizes, keep, coefs))


def server_mix_scatter_math(prev, vals, idx, sizes, keep, coefs):
    """Oracle of the sync plane consuming TOP-K SPARSIFIED client
    deltas: row k keeps its kk largest-magnitude delta elements,
    shipped as (value, flat position) pairs, scatter-accumulated
    against the dense previous model (same mix algebra as
    ``server_mix_delta_math``). The server plane itself densifies the
    pairs and runs the delta plane (``server_plane.
    server_mix_compressed_tree``); this independent formulation is what
    that route is checked against.

    prev: (n,); vals: (K, kk) f32; idx: (K, kk) int32 flat positions;
    sizes/keep: (K,) f32; coefs: (4,) f32.
    """
    alpha, beta = _mix_schedule(coefs)
    w, tot = _norm_weights(sizes, keep)
    a_eff = jnp.where(tot > 0, alpha, 1.0)
    acc = prev.astype(jnp.float32) * (a_eff + beta * jnp.sum(w))
    for k in range(vals.shape[0]):        # one scatter per client
        acc = acc.at[idx[k].astype(jnp.int32)].add(
            vals[k].astype(jnp.float32) * (beta * w[k]))
    return acc.astype(prev.dtype)


def server_async_coefs(qgamma, sizes, delayed, delays, tq, hyp):
    """The async plane's scalar half (paper Eqs. 6-11): staleness
    weights gamma^- from ``delays``, the ring-buffer gamma bookkeeping
    (enqueue, pop of slot t % Q) and the Eq. 10/11 mix coefficients.

    Returns (c, new_qgamma) with c the flat f32 vector
    ``[a_eff, beta*w (K), onehot (K*Q, row-major), sel (Q), 1-sel (Q),
    gscale]`` that ``async_apply`` indexes (``async_layout`` gives the
    offsets)."""
    K, Q = sizes.shape[0], qgamma.shape[0]
    t, pop = tq[0], tq[1]
    alpha_un = jax.nn.sigmoid(-1.0)             # Eq. 9: 1 - sigmoid(1)
    g = (hyp[3] * jax.nn.sigmoid(-delays.astype(jnp.float32))
         * delayed.astype(jnp.float32))                     # (K,) gamma^-
    arrival = (t + delays) % Q                              # (K,)
    onehot = (arrival[:, None]
              == jax.lax.broadcasted_iota(jnp.int32, (K, Q), 1)
              ).astype(jnp.float32) * g[:, None]            # (K, Q)
    qg = qgamma + jnp.sum(onehot, axis=0)
    sel = (jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)[0] == pop
           ).astype(jnp.float32)                            # (Q,) pop mask
    stale_gamma = jnp.sum(qg * sel)
    new_qgamma = qg * (1.0 - sel)

    A = jnp.minimum(hyp[0] + hyp[1] * t.astype(jnp.float32), hyp[2])
    beta = 1.0 - A
    denom = alpha_un + stale_gamma
    alpha = alpha_un / denom * A                            # Eq. 10
    gscale = A / denom                                      # Eq. 11
    w, tot = _norm_weights(sizes, 1.0 - delayed.astype(jnp.float32))
    a_eff = jnp.where(tot > 0, alpha, alpha + beta)
    c = jnp.concatenate([a_eff[None], beta * w, onehot.reshape(K * Q), sel,
                         1.0 - sel, gscale[None]])
    return c, new_qgamma


def async_layout(K: int, Q: int) -> dict:
    """Offsets of the fields of ``server_async_coefs``'s vector."""
    w = 1
    onehot = w + K
    sel = onehot + K * Q
    keep = sel + Q
    return {"w": w, "onehot": onehot, "sel": sel, "keep": keep,
            "gscale": keep + Q}


def async_apply(prev, rows, qrows, c):
    """The async plane's O(N) half: one sequential pass over the client
    axis feeds BOTH the on-time aggregate and the ring-buffer enqueue
    (each client row is read once), then the slot arriving now is popped
    into the mix. ``rows`` (K, ...), ``qrows`` (Q, ...) f32 and ``c``
    (``server_async_coefs``) are only indexed. Returns (out, [new ring
    row q for q < Q]) — the multiply-add chains fuse into a single XLA
    pass and the per-element op order is blocking-independent."""
    K, Q = rows.shape[0], qrows.shape[0]
    o = async_layout(K, Q)
    acc = prev.astype(jnp.float32) * c[0]
    ring = [qrows[q] for q in range(Q)]
    for k in range(K):
        x = rows[k].astype(jnp.float32)
        acc = acc + x * c[o["w"] + k]
        for q in range(Q):                  # enqueue into arrival slots
            ring[q] = ring[q] + x * c[o["onehot"] + k * Q + q]
    stale = ring[0] * c[o["sel"]]           # pop slot t % Q ...
    for q in range(1, Q):
        stale = stale + ring[q] * c[o["sel"] + q]
    acc = acc + stale * c[o["gscale"]]
    return (acc.astype(prev.dtype),
            [ring[q] * c[o["keep"] + q] for q in range(Q)])


def server_async_math(prev, stacked, qsum, qgamma, sizes, delayed, delays,
                      tq, hyp):
    """The async server plane (paper Eqs. 6-11) in one pass: staleness
    weights gamma^- from ``delays``, ring-buffer enqueue of this round's
    delayed updates, pop of the slot arriving now, and the
    alpha/beta/gamma mix.

    prev: (n,); stacked: (K, n); qsum: (Q, n) f32; qgamma: (Q,) f32;
    sizes/delayed: (K,) f32; delays: (K,) int32; tq: (2,) int32 =
    [t, t % Q] (the slot precomputed so the modulo is shared with the
    enqueue arrivals); hyp: (4,) f32 = [alpha0, eta, alpha_cap,
    staleness_b]. Returns (out, new_qsum, new_qgamma).
    """
    c, new_qgamma = server_async_coefs(qgamma, sizes, delayed, delays, tq,
                                       hyp)
    out, ring = async_apply(prev, stacked, qsum, c)
    return out, jnp.stack(ring), new_qgamma


def server_adam_coefs(sizes, keep, scalars):
    """(K+9,) f32 for the FedOpt plane = [w (K), any-kept flag, b1,
    1-b1, b2, 1-b2, lr, tau, 1-b1**step, 1-b2**step] from scalars =
    [b1, b2, lr, tau, step]: the bias corrections' ``pow`` stays in XLA,
    out of the kernel."""
    b1, b2, lr, tau, step = (scalars[i] for i in range(5))
    w, tot = _norm_weights(sizes, keep)
    return jnp.concatenate([w, jnp.stack([
        (tot > 0).astype(jnp.float32), b1, 1.0 - b1, b2, 1.0 - b2, lr, tau,
        1.0 - b1 ** step, 1.0 - b2 ** step])])


def adam_apply(prev, rows, m, v, c):
    """The FedOpt plane's O(N) half: weighted pseudo-gradient, one
    server-Adam moment update and the model step. Returns (out, new_m,
    new_v)."""
    K = rows.shape[0]
    agg = rows[0].astype(jnp.float32) * c[0]  # same fused-chain pattern
    for k in range(1, K):                     # as mix_apply
        agg = agg + rows[k].astype(jnp.float32) * c[k]
    return adam_update(prev, agg, m, v, c, K)


def adam_update(prev, agg, m, v, c, K: int):
    """The Adam step on an aggregated client model ``agg`` (f32), with
    the scalars at ``c[K:]`` of ``server_adam_coefs``' vector."""
    kept, b1, nb1, b2, nb2, lr, tau, bc1, bc2 = (c[K + i] for i in range(9))
    p32 = prev.astype(jnp.float32)
    delta = jnp.where(kept > 0, agg - p32, 0.0)
    new_m = b1 * m + nb1 * delta
    new_v = b2 * v + nb2 * delta * delta
    update = (new_m / bc1) / (jnp.sqrt(new_v / bc2) + tau)
    return (p32 + lr * update).astype(prev.dtype), new_m, new_v


def server_adam_math(prev, stacked, m, v, sizes, keep, scalars):
    """The FedOpt server plane: weighted pseudo-gradient + one server-Adam
    moment update + the model step, one pass.

    prev: (n,); stacked: (K, n); m/v: (n,) f32; sizes/keep: (K,) f32;
    scalars: (5,) f32 = [b1, b2, lr, tau, step] (step ALREADY
    incremented). Returns (out, new_m, new_v).
    """
    return adam_apply(prev, stacked, m, v,
                      server_adam_coefs(sizes, keep, scalars))


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """Plain softmax attention. q/k/v: (B, S, H, hd) (kv already repeated)."""
    B, S, H, hd = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = kpos <= qpos
    if window:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
