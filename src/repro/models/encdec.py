"""Whisper-style encoder-decoder backbone (arXiv:2212.04356).

Per the assignment the mel/conv frontend is a STUB: ``input_specs`` hands the
model precomputed frame embeddings (B, encoder_seq, d_model). We implement
the transformer backbone: non-causal encoder, causal decoder with
cross-attention, cached decode (self-KV ring + precomputed cross-KV).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.layers import (dense, dense_init, embedding,
                                 embedding_init, mlp, mlp_init,
                                 rmsnorm, rmsnorm_init)


def _enc_block_init(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    return {"ln1": rmsnorm_init(cfg.d_model, dtype),
            "attn": attn.attn_init(ks[0], cfg, dtype),
            "ln2": rmsnorm_init(cfg.d_model, dtype),
            "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype, cfg.mlp_act)}


def _dec_block_init(key, cfg, dtype):
    ks = jax.random.split(key, 3)
    return {"ln1": rmsnorm_init(cfg.d_model, dtype),
            "self_attn": attn.attn_init(ks[0], cfg, dtype),
            "ln_x": rmsnorm_init(cfg.d_model, dtype),
            "cross_attn": attn.attn_init(ks[1], cfg, dtype),
            "ln2": rmsnorm_init(cfg.d_model, dtype),
            "mlp": mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype, cfg.mlp_act)}


def _stack(key, n, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    n_tail = min(cfg.fes_tail_layers, cfg.num_layers)
    n_body = cfg.num_layers - n_tail
    return {
        "enc_pos": 0.02 * jax.random.normal(
            ks[0], (cfg.encoder_seq, cfg.d_model), jnp.float32).astype(dtype),
        "encoder": _stack(ks[1], cfg.encoder_layers,
                          lambda k: _enc_block_init(k, cfg, dtype)),
        "enc_norm": rmsnorm_init(cfg.d_model, dtype),
        "embed": embedding_init(ks[2], cfg.vocab_size, cfg.d_model, dtype),
        "body": _stack(ks[3], n_body, lambda k: _dec_block_init(k, cfg, dtype)),
        "tail": _stack(ks[4], n_tail, lambda k: _dec_block_init(k, cfg, dtype)),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
        "lm_head": dense_init(ks[5], cfg.d_model, cfg.vocab_size, dtype),
    }


def encode(params, cfg, frame_emb):
    x = frame_emb.astype(jnp.dtype(cfg.dtype)) + params["enc_pos"][None]
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, p):
        h = attn.attention_fwd(p["attn"], cfg, rmsnorm(p["ln1"], x), pos,
                               causal=False, window=0)
        x = x + h
        return x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.mlp_act), None

    if cfg.remat:
        body = jax.checkpoint(body)
    n = jax.tree.leaves(params["encoder"])[0].shape[0]
    x, _ = jax.lax.scan(body, x, params["encoder"],
                        unroll=n if cfg.unroll_layers else 1)
    return rmsnorm(params["enc_norm"], x)


def _dec_block_fwd(p, cfg, x, pos, enc_out, enc_pos):
    h = attn.attention_fwd(p["self_attn"], cfg, rmsnorm(p["ln1"], x), pos)
    x = x + h
    h = attn.attention_fwd(p["cross_attn"], cfg, rmsnorm(p["ln_x"], x), pos,
                           causal=False, kv_x=enc_out, kv_positions=enc_pos,
                           window=0)
    x = x + h
    return x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.mlp_act)


def _dec_scan(stacked, cfg, x, pos, enc_out, enc_pos):
    def body(x, p):
        return _dec_block_fwd(p, cfg, x, pos, enc_out, enc_pos), None
    if cfg.remat:
        body = jax.checkpoint(body)
    n = jax.tree.leaves(stacked)[0].shape[0]
    x, _ = jax.lax.scan(body, x, stacked,
                        unroll=n if cfg.unroll_layers else 1)
    return x


def forward(params, cfg, batch):
    """batch: {"frame_emb": (B, enc_seq, d), "tokens": (B, S)}."""
    enc_out = encode(params, cfg, batch["frame_emb"])
    B, Se, _ = enc_out.shape
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    x = embedding(params["embed"], batch["tokens"])
    S = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _dec_scan(params["body"], cfg, x, pos, enc_out, enc_pos)
    x = _dec_scan(params["tail"], cfg, x, pos, enc_out, enc_pos)
    x = rmsnorm(params["final_norm"], x)
    return dense(params["lm_head"], x), jnp.float32(0.0)


def hidden_states(params, cfg, batch):
    enc_out = encode(params, cfg, batch["frame_emb"])
    B, Se, _ = enc_out.shape
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    x = embedding(params["embed"], batch["tokens"])
    S = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _dec_scan(params["body"], cfg, x, pos, enc_out, enc_pos)
    x = _dec_scan(params["tail"], cfg, x, pos, enc_out, enc_pos)
    return rmsnorm(params["final_norm"], x)


def loss_fn(params, cfg, batch):
    from repro.models.layers import chunked_cross_entropy
    x = hidden_states(params, cfg, batch)
    tokens = batch["tokens"]
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:]), jnp.zeros_like(tokens[:, :1])], axis=1)
    return chunked_cross_entropy(x, params["lm_head"], labels, mask,
                                 unroll=cfg.unroll_chunks)


def prefill_logits(params, cfg, batch):
    x = hidden_states(params, cfg, batch)
    return dense(params["lm_head"], x[:, -1, :])


# ------------------------------------------------------------- decode ------

def _split_kv(p, cfg, enc_out):
    hd = cfg.resolved_head_dim
    k = dense(p["wk"], enc_out).reshape(*enc_out.shape[:-1], cfg.num_kv_heads, hd)
    v = dense(p["wv"], enc_out).reshape(*enc_out.shape[:-1], cfg.num_kv_heads, hd)
    return k, v


def init_decode_cache(params, cfg, frame_emb, max_len, dtype=None):
    """Encode once; precompute per-layer cross-KV; fresh self-KV rings."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    enc_out = encode(params, cfg, frame_emb)
    B = enc_out.shape[0]

    def cross_kv(stacked):
        def one(p):
            return _split_kv(p["cross_attn"], cfg, enc_out)
        return jax.vmap(one, in_axes=(0,))(stacked)      # (L, B, Se, KH, hd)

    def self_kv(stacked):
        L = jax.tree.leaves(stacked)[0].shape[0]
        return jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[attn.init_kv_cache(cfg, B, max_len, dtype) for _ in range(L)])

    return {
        "body_self": self_kv(params["body"]),
        "tail_self": self_kv(params["tail"]),
        "body_cross": cross_kv(params["body"]),
        "tail_cross": cross_kv(params["tail"]),
    }


def _dec_scan_decode(stacked, cfg, x, position, self_c, cross_c):
    def body(x, inp):
        p, sc, cc = inp
        h, sc = attn.attention_decode(p["self_attn"], cfg,
                                      rmsnorm(p["ln1"], x), sc, position)
        x = x + h
        ck, cv = cc
        h = attn.cross_attention_decode(p["cross_attn"], cfg,
                                        rmsnorm(p["ln_x"], x), ck, cv)
        x = x + h
        x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.mlp_act)
        return x, sc

    n = jax.tree.leaves(stacked)[0].shape[0]
    x, self_c = jax.lax.scan(body, x, (stacked, self_c, cross_c),
                             unroll=n if cfg.unroll_layers else 1)
    return x, self_c


def decode_step(params, cfg, token, position, cache):
    x = embedding(params["embed"], token[:, None])
    x, body_self = _dec_scan_decode(params["body"], cfg, x, position,
                                    cache["body_self"], cache["body_cross"])
    x, tail_self = _dec_scan_decode(params["tail"], cfg, x, position,
                                    cache["tail_self"], cache["tail_cross"])
    x = rmsnorm(params["final_norm"], x)
    logits = dense(params["lm_head"], x)[:, 0]
    return logits, dict(cache, body_self=body_self, tail_self=tail_self)


# ---------------------------------------------------- chunked prefill ------

def _dec_scan_prefill(stacked, cfg, x, positions, self_c, cross_c):
    def body(x, inp):
        p, sc, cc = inp
        h, sc = attn.attention_prefill(p["self_attn"], cfg,
                                       rmsnorm(p["ln1"], x), sc, positions)
        x = x + h
        ck, cv = cc
        h = attn.cross_attention_decode(p["cross_attn"], cfg,
                                        rmsnorm(p["ln_x"], x), ck, cv)
        x = x + h
        x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.mlp_act)
        return x, sc

    n = jax.tree.leaves(stacked)[0].shape[0]
    x, self_c = jax.lax.scan(body, x, (stacked, self_c, cross_c),
                             unroll=n if cfg.unroll_layers else 1)
    return x, self_c


def prefill(params, cfg, tokens, positions, cache):
    """Chunked decoder prefill against the cached decode state (self-KV
    rings written blockwise; cross-KV read batched). tokens/positions:
    (B, c); pad rows carry positions >= attn.PAD_FLOOR. Returns (logits
    (B, c, V), cache) bit-identical to the per-token decode loop."""
    x = embedding(params["embed"], tokens)
    x, body_self = _dec_scan_prefill(params["body"], cfg, x, positions,
                                     cache["body_self"], cache["body_cross"])
    x, tail_self = _dec_scan_prefill(params["tail"], cfg, x, positions,
                                     cache["tail_self"], cache["tail_cross"])
    x = rmsnorm(params["final_norm"], x)
    logits = dense(params["lm_head"], x)
    return logits, dict(cache, body_self=body_self, tail_self=tail_self)
