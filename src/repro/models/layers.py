"""Core neural-net primitives (pure JAX, functional params-as-pytrees)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def uniform_init(key, shape, scale, dtype):
    return jax.random.uniform(key, shape, dtype=jnp.float32, minval=-scale,
                              maxval=scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype, bias: bool = False):
    """Fan-in scaled init (matches torch.nn.Linear default scale)."""
    scale = (1.0 / d_in) ** 0.5
    p = {"w": uniform_init(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def embedding_init(key, vocab, d, dtype):
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32).astype(dtype) * 0.02}


def embedding(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def rmsnorm_init(d, dtype):
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d, dtype):
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)


def layernorm1p_init(d, dtype):
    """LayerNorm1p (Nemotron): the stored scale is ``weight`` and the
    applied one ``weight + 1``, so both start at zero."""
    return {"g": jnp.zeros((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm1p(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * (p["g"].astype(jnp.float32) + 1.0)
            + p["b"].astype(jnp.float32)).astype(x.dtype)


def norm_init(cfg, d, dtype):
    """The block norm the config names (``cfg.norm``)."""
    if cfg.norm == "layernorm1p":
        return layernorm1p_init(d, dtype)
    if cfg.norm == "rmsnorm":
        return rmsnorm_init(d, dtype)
    raise ValueError(f"unknown norm {cfg.norm!r}")


def norm(cfg, p, x):
    return layernorm1p(p, x) if cfg.norm == "layernorm1p" else rmsnorm(p, x)


# ---------------------------------------------------------------- rotary ----

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float, rotary_dim: int = 0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S). Rotates
    the leading ``rotary_dim`` dims of each head (0: all of them) and
    passes the rest through (partial rotary, as in Nemotron)."""
    hd = x.shape[-1]
    rd = rotary_dim or hd
    freqs = rope_freqs(rd, theta)                       # (rd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, rd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, rd/2)
    sin = jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf[..., :rd], 2, axis=-1)
    parts = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if rd < hd:
        parts.append(xf[..., rd:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


# ------------------------------------------------------------------- MLP ----

def mlp_init(key, d_model, d_ff, dtype, act: str = "swiglu"):
    ks = jax.random.split(key, 3)
    p = {"w_in": dense_init(ks[0], d_model, d_ff, dtype),
         "w_out": dense_init(ks[1], d_ff, d_model, dtype)}
    if act == "swiglu":
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp(p, x, act: str = "swiglu"):
    """``act``: swiglu (gated SiLU), gelu, or relu2 (squared ReLU)."""
    h = dense(p["w_in"], x)
    if act == "swiglu":
        h = jax.nn.silu(dense(p["w_gate"], x)) * h
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(f"unknown mlp_act {act!r}")
    return dense(p["w_out"], h)


def cross_entropy_loss(logits, labels, mask=None):
    """Mean token-level cross entropy in f32. logits (..., V), labels (...).

    The gold-logit pick is an iota-compare masked reduction (NOT
    take_along_axis): it fuses into the vocab reduction and stays sharded
    when V lives on the "model" mesh axis, instead of forcing GSPMD to
    replicate the full logits for a gather.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    iota = jax.lax.broadcasted_iota(labels.dtype, logits.shape,
                                    logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), axis=-1)
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_cross_entropy(x, head, labels, mask, *, chunk: int = 1024,
                          unroll: bool = False):
    """Sequence-chunked CE: logits are materialised one seq-chunk at a time
    (per-chunk remat), so peak memory is O(B * chunk * V) instead of
    O(B * S * V) — the dominant temp buffer for large-vocab archs.

    x: (B, S, d) final hidden states; head: lm_head param dict;
    labels/mask: (B, S). Returns mean nll over mask.
    """
    B, S, d = x.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = x.shape[1] // chunk
    xc = jnp.moveaxis(x.reshape(B, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)

    def body(tot, inp):
        xs, ls, ms = inp
        logits = dense(head, xs).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        iota = jax.lax.broadcasted_iota(ls.dtype, logits.shape, 2)
        gold = jnp.sum(jnp.where(iota == ls[..., None], logits, 0.0), -1)
        msf = ms.astype(jnp.float32)
        return tot + jnp.sum((logz - gold) * msf), None

    body_ck = jax.checkpoint(body)
    tot, _ = jax.lax.scan(body_ck, jnp.float32(0.0), (xc, lc, mc),
                          unroll=n if unroll else 1)
    return tot / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
