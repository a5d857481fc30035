"""RWKV-6 ("Finch") block: data-dependent decay linear attention.

Faithful to arXiv:2404.05892 at the block level:
  * token shift (learned per-channel lerp with previous token),
  * low-rank data-dependent decay  w_t = exp(-exp(w0 + tanh(x A) B)),
  * per-head state recurrence  S_t = diag(w_t) S_{t-1} + k_t^T v_t,
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t),
  * per-head group-norm, silu(g) gate, output projection,
  * squared-ReLU channel mixing.

The recurrence runs as a lax.scan over time (projections are computed for
the whole sequence in parallel; only the O(d*hd) state update is serial).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense, dense_init, layernorm

HEAD_DIM = 64
DECAY_RANK = 32


def rwkv6_init(key, cfg, dtype):
    d = cfg.d_model
    H = d // HEAD_DIM
    ks = jax.random.split(key, 10)
    return {
        "mix": 0.5 * jnp.ones((5, d), dtype),            # r,k,v,w,g token-shift
        "wr": dense_init(ks[0], d, d, dtype),
        "wk": dense_init(ks[1], d, d, dtype),
        "wv": dense_init(ks[2], d, d, dtype),
        "wg": dense_init(ks[3], d, d, dtype),
        "wo": dense_init(ks[4], d, d, dtype),
        # data-dependent decay (low-rank)
        "w0": jnp.full((d,), -4.0, dtype),
        "wA": dense_init(ks[5], d, DECAY_RANK, dtype),
        "wB": dense_init(ks[6], DECAY_RANK, d, dtype),
        "u": jnp.zeros((H, HEAD_DIM), dtype),            # bonus
        "ln_x": {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
        # channel mix
        "cmix": 0.5 * jnp.ones((2, d), dtype),
        "ck": dense_init(ks[7], d, int(3.5 * d) if cfg.d_ff == 0 else cfg.d_ff, dtype),
        "cv": dense_init(ks[8], int(3.5 * d) if cfg.d_ff == 0 else cfg.d_ff, d, dtype),
        "cr": dense_init(ks[9], d, d, dtype),
    }


def _token_shift(x, x_prev_last):
    """shift x right by one along seq; position 0 gets x_prev_last."""
    return jnp.concatenate([x_prev_last[:, None, :], x[:, :-1, :]], axis=1)


def _decay(p, xw):
    lr = jnp.tanh(dense(p["wA"], xw)) @ p["wB"]["w"]
    logw = -jnp.exp(
        jnp.clip(p["w0"].astype(jnp.float32) + lr.astype(jnp.float32), -8.0, 4.0))
    return jnp.exp(logw)                                 # in (0, 1)


def init_rwkv_state(cfg, batch, dtype):
    d = cfg.d_model
    H = d // HEAD_DIM
    return {
        "wkv": jnp.zeros((batch, H, HEAD_DIM, HEAD_DIM), jnp.float32),
        "x_tm": jnp.zeros((batch, d), dtype),            # time-mix shift state
        "x_cm": jnp.zeros((batch, d), dtype),            # channel-mix shift state
    }


def time_mix(p, cfg, x, state):
    """Full-sequence forward. x: (B, S, d). Returns (y, new_state)."""
    B, S, d = x.shape
    H = d // HEAD_DIM
    xs = _token_shift(x, state["x_tm"])
    mixed = [x + p["mix"][i] * (xs - x) for i in range(5)]
    xr, xk, xv, xw, xg = mixed
    r = dense(p["wr"], xr).reshape(B, S, H, HEAD_DIM)
    k = dense(p["wk"], xk).reshape(B, S, H, HEAD_DIM)
    v = dense(p["wv"], xv).reshape(B, S, H, HEAD_DIM)
    g = dense(p["wg"], xg)
    w = _decay(p, xw).reshape(B, S, H, HEAD_DIM)         # (0,1)

    u = p["u"].astype(jnp.float32)

    def step(S_, inp):
        r_t, k_t, v_t, w_t = inp                         # (B,H,hd) each
        r_t = r_t.astype(jnp.float32)                    # f32 inside the
        k_t = k_t.astype(jnp.float32)                    # step only: scan
        v_t = v_t.astype(jnp.float32)                    # inputs stay bf16
        w_t = w_t.astype(jnp.float32)                    # (halves the
        kv = k_t[..., :, None] * v_t[..., None, :]       # resharding bytes
        y = jnp.einsum("bhi,bhij->bhj", r_t, S_ + u[..., None] * kv)
        S_ = w_t[..., :, None] * S_ + kv                 # around the head
        return S_, y                                     # reshape, §Perf H2)

    # Two-level chunked scan: outer scan saves the O(H*hd*hd) state only at
    # chunk boundaries (per-chunk remat), so training backward memory is
    # O(S/chunk) states instead of O(S) — the TPU adaptation of the CUDA
    # wkv kernel's chunked recomputation.
    CH = 64
    pad = (-S) % CH
    def prep(a):
        a = jnp.moveaxis(a, 1, 0)                        # (S,B,H,hd)
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((S + pad) // CH, CH, *a.shape[1:])
    rs, ks_, vs, ws = prep(r), prep(k), prep(v), prep(w)
    # pad decay with ones so padded steps keep the state unchanged
    if pad:
        ws = ws.at[-1, CH - pad:].set(jnp.asarray(1.0, ws.dtype))

    @jax.checkpoint
    def chunk_step(S_, inp):
        return jax.lax.scan(step, S_, inp)

    S_new, ys = jax.lax.scan(chunk_step, state["wkv"], (rs, ks_, vs, ws))
    ys = ys.reshape(S + pad, B, H, HEAD_DIM)[:S]
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, d).astype(x.dtype)

    y = layernorm(p["ln_x"], y)                          # group-norm proxy
    y = y * jax.nn.silu(g)
    out = dense(p["wo"], y)
    new_state = dict(state, wkv=S_new, x_tm=x[:, -1, :])
    return out, new_state


def time_mix_step(p, cfg, x, state):
    """Single-token decode. x: (B, d)."""
    B, d = x.shape
    H = d // HEAD_DIM
    xs = state["x_tm"]
    mixed = [x + p["mix"][i] * (xs - x) for i in range(5)]
    xr, xk, xv, xw, xg = mixed
    r = dense(p["wr"], xr).reshape(B, H, HEAD_DIM).astype(jnp.float32)
    k = dense(p["wk"], xk).reshape(B, H, HEAD_DIM).astype(jnp.float32)
    v = dense(p["wv"], xv).reshape(B, H, HEAD_DIM).astype(jnp.float32)
    g = dense(p["wg"], xg)
    w = _decay(p, xw).reshape(B, H, HEAD_DIM)
    u = p["u"].astype(jnp.float32)
    kv = k[..., :, None] * v[..., None, :]
    y = jnp.einsum("bhi,bhij->bhj", r, state["wkv"] + u[..., None] * kv)
    S_new = w[..., :, None] * state["wkv"] + kv
    y = y.reshape(B, d).astype(x.dtype)
    y = layernorm(p["ln_x"], y) * jax.nn.silu(g)
    out = dense(p["wo"], y)
    return out, dict(state, wkv=S_new, x_tm=x)


def channel_mix(p, x, state, single: bool = False):
    if single:
        xs = state["x_cm"]
        new_last = x
    else:
        xs = _token_shift(x, state["x_cm"])
        new_last = x[:, -1, :]
    xk = x + p["cmix"][0] * (xs - x)
    xr = x + p["cmix"][1] * (xs - x)
    k = jnp.square(jax.nn.relu(dense(p["ck"], xk)))
    out = jax.nn.sigmoid(dense(p["cr"], xr)) * dense(p["cv"], k)
    return out, dict(state, x_cm=new_last)
