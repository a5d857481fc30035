"""Parameterised decoder stack covering dense / MoE / VLM / RWKV6 / hybrid.

The stack is split into BODY and TAIL block groups so the paper's FES
scheme (feature extractor = embed + body; classifier = tail + final norm +
lm head) is a first-class param-tree boundary, not an afterthought.

Homogeneous blocks are stacked along a leading layer axis and applied with
``lax.scan`` — keeps HLO size O(1) in depth (126-layer archs compile fast).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba2, moe, rwkv6
from repro.models.layers import (dense, dense_init, embedding, embedding_init,
                                 mlp, mlp_init, norm, norm_init)


# ------------------------------------------------------------- blocks ------

def block_init(key, cfg, dtype):
    """One block of the arch's family."""
    if cfg.family == "ssm":                       # rwkv6
        return {"rwkv": rwkv6.rwkv6_init(key, cfg, dtype),
                "ln1": norm_init(cfg, cfg.d_model, dtype),
                "ln2": norm_init(cfg, cfg.d_model, dtype)}
    if cfg.family == "hybrid":                    # zamba2 mamba block
        return {"mamba": mamba2.mamba2_init(key, cfg, dtype),
                "ln": norm_init(cfg, cfg.d_model, dtype)}
    ks = jax.random.split(key, 2)
    p = {"ln1": norm_init(cfg, cfg.d_model, dtype),
         "ln2": norm_init(cfg, cfg.d_model, dtype),
         "attn": attn.attn_init(ks[0], cfg, dtype)}
    if cfg.num_experts:
        p["moe"] = moe.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype, cfg.mlp_act)
    return p


def _stacked_block_init(key, cfg, n, dtype):
    keys = jax.random.split(key, max(n, 1))[:n]
    if n == 0:
        return None
    return jax.vmap(lambda k: block_init(k, cfg, dtype))(keys)


def block_fwd(p, cfg, x, positions, aux):
    """Full-sequence block application. Returns (x, aux)."""
    if cfg.family == "ssm":
        B = x.shape[0]
        st = rwkv6.init_rwkv_state(cfg, B, x.dtype)
        h, st = rwkv6.time_mix(p["rwkv"], cfg, norm(cfg, p["ln1"], x), st)
        x = x + h
        h, _ = rwkv6.channel_mix(p["rwkv"], norm(cfg, p["ln2"], x), st)
        return x + h, aux
    if cfg.family == "hybrid":
        B = x.shape[0]
        st = mamba2.init_mamba_state(cfg, B, x.dtype)
        h, _ = mamba2.mamba2_fwd(p["mamba"], cfg, norm(cfg, p["ln"], x), st)
        return x + h, aux
    h = attn.attention_fwd(p["attn"], cfg, norm(cfg, p["ln1"], x), positions)
    x = x + h
    if cfg.num_experts:
        h, a = moe.moe_apply(p["moe"], cfg, norm(cfg, p["ln2"], x))
        aux = aux + a
    else:
        h = mlp(p["mlp"], norm(cfg, p["ln2"], x), cfg.mlp_act)
    return x + h, aux


def _scan_blocks(stacked, cfg, x, positions, aux, shared_attn=None):
    """Apply a stacked group of blocks with lax.scan (+remat)."""
    if stacked is None:
        return x, aux

    def body(carry, layer_p):
        x, aux = carry
        if cfg.shard_residuals:
            # the scan carry is what checkpoint saves per layer: keep it
            # model-sharded so the residual stack is 16x smaller
            from repro.sharding.ctx import constrain
            x = constrain(x, None, None, "model")
        x, aux = block_fwd(layer_p, cfg, x, positions, aux)
        return (x, aux), None

    if cfg.remat:
        body = jax.checkpoint(body)

    def _scan(f, c, xs):
        n = jax.tree.leaves(xs)[0].shape[0]
        return jax.lax.scan(f, c, xs, unroll=n if cfg.unroll_layers else 1)

    L = jax.tree.leaves(stacked)[0].shape[0]
    if (cfg.family == "hybrid" and cfg.attn_every and shared_attn is not None
            and L >= cfg.attn_every):
        # group the mamba blocks; apply the SHARED attention block between
        # groups (Zamba2: one attention param set reused across depth).
        per = cfg.attn_every
        G = L // per
        rest = L - G * per
        grouped = jax.tree.map(
            lambda a: a[: G * per].reshape(G, per, *a.shape[1:]), stacked)

        def group_body(carry, group_p):
            x, aux = carry
            (x, aux), _ = _scan(body, (x, aux), group_p)
            h = attn.attention_fwd(
                shared_attn["attn"], cfg, norm(cfg, shared_attn["ln"], x),
                positions)
            return (x + h, aux), None

        (x, aux), _ = _scan(group_body, (x, aux), grouped)
        if rest:
            tail_p = jax.tree.map(lambda a: a[G * per:], stacked)
            (x, aux), _ = _scan(body, (x, aux), tail_p)
        return x, aux

    (x, aux), _ = _scan(body, (x, aux), stacked)
    return x, aux


# ------------------------------------------------------------- params ------

def init_params(cfg, key):
    dtype = jnp.dtype(cfg.param_dtype or cfg.dtype)
    ks = jax.random.split(key, 8)
    n_tail = min(cfg.fes_tail_layers, cfg.num_layers)
    n_body = cfg.num_layers - n_tail
    params = {
        "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "body": _stacked_block_init(ks[1], cfg, n_body, dtype),
        "tail": _stacked_block_init(ks[2], cfg, n_tail, dtype),
        "final_norm": norm_init(cfg, cfg.d_model, dtype),
        "lm_head": dense_init(ks[3], cfg.d_model, cfg.vocab_size, dtype),
    }
    if cfg.family == "hybrid" and cfg.attn_every:
        acfg = cfg.with_(num_heads=cfg.num_heads or 32,
                         num_kv_heads=cfg.num_kv_heads or 32)
        params["shared_attn"] = {
            "attn": attn.attn_init(ks[4], acfg, dtype),
            "ln": norm_init(cfg, cfg.d_model, dtype),
        }
    if cfg.family == "vlm":
        params["vision_proj"] = dense_init(
            ks[5], cfg.vision_dim or cfg.d_model, cfg.d_model, dtype)
    return params


# ------------------------------------------------------------ forward ------

def embed_inputs(params, cfg, batch):
    """Returns (x, positions, label_offset). VLM prepends patch embeddings."""
    tokens = batch["tokens"]
    x = embedding(params["embed"], tokens)
    if cfg.family == "vlm":
        pe = dense(params["vision_proj"], batch["patch_emb"].astype(x.dtype))
        x = jnp.concatenate([pe, x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, positions


def forward(params, cfg, batch):
    """Full-sequence logits (train / prefill)."""
    x, positions = embed_inputs(params, cfg, batch)
    aux = jnp.float32(0.0)
    x, aux = _scan_blocks(params["body"], cfg, x, positions, aux,
                          params.get("shared_attn"))
    x, aux = _scan_blocks(params["tail"], cfg, x, positions, aux,
                          params.get("shared_attn"))
    x = norm(cfg, params["final_norm"], x)
    logits = dense(params["lm_head"], x)
    return logits, aux


def hidden_states(params, cfg, batch):
    """Final-norm hidden states (no logits)."""
    x, positions = embed_inputs(params, cfg, batch)
    aux = jnp.float32(0.0)
    x, aux = _scan_blocks(params["body"], cfg, x, positions, aux,
                          params.get("shared_attn"))
    x, aux = _scan_blocks(params["tail"], cfg, x, positions, aux,
                          params.get("shared_attn"))
    return norm(cfg, params["final_norm"], x), aux


def loss_fn(params, cfg, batch):
    """Next-token CE (+ MoE aux), chunked over the sequence so the logits
    never materialise at (B, S, V). VLM: loss on the text segment only."""
    from repro.models.layers import chunked_cross_entropy
    x, aux = hidden_states(params, cfg, batch)
    tokens = batch["tokens"]
    if cfg.family == "vlm":
        x = x[:, -tokens.shape[1]:, :]
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:]), jnp.zeros_like(tokens[:, :1])], axis=1)
    loss = chunked_cross_entropy(x, params["lm_head"], labels, mask,
                                 unroll=cfg.unroll_chunks)
    return loss + 0.01 * aux


def prefill_logits(params, cfg, batch):
    """Full-sequence prefill, last-position logits only (dry-run costing;
    full (B, S, V) logits are never formed). The cache-writing chunked
    prefill for serving is ``prefill`` below."""
    x, _ = hidden_states(params, cfg, batch)
    return dense(params["lm_head"], x[:, -1, :])


# ------------------------------------------------------------- decode ------

def init_decode_cache(cfg, batch, max_len, dtype=None):
    """Per-layer decode state stacked along the layer axis."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_tail = min(cfg.fes_tail_layers, cfg.num_layers)
    n_body = cfg.num_layers - n_tail

    def one(_):
        if cfg.family == "ssm":
            return rwkv6.init_rwkv_state(cfg, batch, dtype)
        if cfg.family == "hybrid":
            return mamba2.init_mamba_state(cfg, batch, dtype)
        return attn.init_kv_cache(cfg, batch, max_len, dtype)

    def stack(n):
        if n == 0:
            return None
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[one(i) for i in range(n)])

    cache = {"body": stack(n_body), "tail": stack(n_tail)}
    if cfg.family == "hybrid" and cfg.attn_every:
        G = n_body // cfg.attn_every  # shared-attn KV caches (one per group site)
        if G > 0:
            cache["shared"] = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[attn.init_kv_cache(cfg, batch, max_len, dtype)
                  for _ in range(G)])
    return cache


def block_decode(p, cfg, x, cache, position):
    """One-token block application. x: (B, 1, d)."""
    if cfg.family == "ssm":
        h, cache = rwkv6.time_mix_step(p["rwkv"], cfg,
                                       norm(cfg, p["ln1"], x)[:, 0], cache)
        x = x + h[:, None]
        h, cache = rwkv6.channel_mix(p["rwkv"], norm(cfg, p["ln2"], x)[:, 0],
                                     cache, single=True)
        return x + h[:, None], cache
    if cfg.family == "hybrid":
        h, cache = mamba2.mamba2_step(p["mamba"], cfg,
                                      norm(cfg, p["ln"], x)[:, 0], cache)
        return x + h[:, None], cache
    h, cache = attn.attention_decode(p["attn"], cfg, norm(cfg, p["ln1"], x),
                                     cache, position)
    x = x + h
    if cfg.num_experts:
        h, _ = moe.moe_apply_dense(p["moe"], cfg, norm(cfg, p["ln2"], x))
    else:
        h = mlp(p["mlp"], norm(cfg, p["ln2"], x), cfg.mlp_act)
    return x + h, cache


def _scan_blocks_decode(stacked, cfg, x, cache, position, shared_attn=None,
                        shared_cache=None):
    if stacked is None:
        return x, cache, shared_cache

    def body(carry, inp):
        x = carry
        layer_p, layer_c = inp
        x, layer_c = block_decode(layer_p, cfg, x, layer_c, position)
        return x, layer_c

    def _scan(f, c, xs):
        n = jax.tree.leaves(xs)[0].shape[0]
        return jax.lax.scan(f, c, xs, unroll=n if cfg.unroll_layers else 1)

    L = jax.tree.leaves(stacked)[0].shape[0]
    if (cfg.family == "hybrid" and cfg.attn_every and shared_attn is not None
            and shared_cache is not None and L >= cfg.attn_every):
        per = cfg.attn_every
        G = L // per
        grouped_p = jax.tree.map(
            lambda a: a[: G * per].reshape(G, per, *a.shape[1:]), stacked)
        grouped_c = jax.tree.map(
            lambda a: a[: G * per].reshape(G, per, *a.shape[1:]), cache)

        def group_body(x, inp):
            gp, gc, sc = inp
            x, gc = _scan(body, x, (gp, gc))
            h, sc = attn.attention_decode(
                shared_attn["attn"], cfg, norm(cfg, shared_attn["ln"], x), sc,
                position)
            return x + h, (gc, sc)

        x, (grouped_c, shared_cache) = _scan(
            group_body, x, (grouped_p, grouped_c, shared_cache))
        new_cache = jax.tree.map(
            lambda a: a.reshape(G * per, *a.shape[2:]), grouped_c)
        rest = L - G * per
        if rest:
            tail_p = jax.tree.map(lambda a: a[G * per:], stacked)
            tail_c = jax.tree.map(lambda a: a[G * per:], cache)
            x, tail_c = _scan(body, x, (tail_p, tail_c))
            new_cache = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], 0), new_cache, tail_c)
        return x, new_cache, shared_cache

    x, cache = _scan(body, x, (stacked, cache))
    return x, cache, shared_cache


def decode_step(params, cfg, token, position, cache):
    """token: (B,) int32; position: (B,). Returns (logits (B, V), cache)."""
    x = embedding(params["embed"], token[:, None])
    x, body_c, shared_c = _scan_blocks_decode(
        params["body"], cfg, x, cache["body"], position,
        params.get("shared_attn"), cache.get("shared"))
    x, tail_c, _ = _scan_blocks_decode(
        params["tail"], cfg, x, cache["tail"], position)
    x = norm(cfg, params["final_norm"], x)
    logits = dense(params["lm_head"], x)[:, 0]
    new_cache = {"body": body_c, "tail": tail_c}
    if shared_c is not None:
        new_cache["shared"] = shared_c
    return logits, new_cache


# ---------------------------------------------------- chunked prefill ------

def block_prefill(p, cfg, x, cache, positions):
    """One prompt chunk through one block. x: (B, c, d). Attention-family
    blocks only (ssm/hybrid keep the per-token path); the FFN half reuses
    the decode-path ops (moe_apply_dense / mlp) so the residual stream
    matches ``block_decode`` bitwise row-for-row."""
    h, cache = attn.attention_prefill(p["attn"], cfg, norm(cfg, p["ln1"], x),
                                      cache, positions)
    x = x + h
    if cfg.num_experts:
        h, _ = moe.moe_apply_dense(p["moe"], cfg, norm(cfg, p["ln2"], x))
    else:
        h = mlp(p["mlp"], norm(cfg, p["ln2"], x), cfg.mlp_act)
    return x + h, cache


def _scan_blocks_prefill(stacked, cfg, x, cache, positions):
    if stacked is None:
        return x, cache

    def body(x, inp):
        layer_p, layer_c = inp
        x, layer_c = block_prefill(layer_p, cfg, x, layer_c, positions)
        return x, layer_c

    n = jax.tree.leaves(stacked)[0].shape[0]
    x, cache = jax.lax.scan(body, x, (stacked, cache),
                            unroll=n if cfg.unroll_layers else 1)
    return x, cache


def prefill(params, cfg, tokens, positions, cache):
    """Jitted chunked prefill: one dispatch per prompt CHUNK instead of
    per token. tokens/positions: (B, c); pad rows carry positions >=
    attn.PAD_FLOOR and never enter the cache. Returns (logits (B, c, V),
    cache) — bit-identical to looping ``decode_step`` over the chunk
    (gated in tests/test_serve_plane.py)."""
    x = embedding(params["embed"], tokens)
    x, body_c = _scan_blocks_prefill(params["body"], cfg, x,
                                     cache["body"], positions)
    x, tail_c = _scan_blocks_prefill(params["tail"], cfg, x,
                                     cache["tail"], positions)
    x = norm(cfg, params["final_norm"], x)
    logits = dense(params["lm_head"], x)
    return logits, {"body": body_c, "tail": tail_c}


# --------------------------------------------------------- paged cache -----

def init_paged_pool(cfg, num_blocks, block_size, dtype=None):
    """Block pool shared by all in-flight requests: per layer-group leaves
    (n_layers, num_blocks, block_size, KH, hd) + pos (n_layers, nb, bs).
    Block 0 is reserved as the null/trash block (block-table entry 0 =
    unmapped)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    n_tail = min(cfg.fes_tail_layers, cfg.num_layers)
    n_body = cfg.num_layers - n_tail

    def group(n):
        if n == 0:
            return None
        return {"k": jnp.zeros((n, num_blocks, block_size,
                                cfg.num_kv_heads, hd), dtype),
                "v": jnp.zeros((n, num_blocks, block_size,
                                cfg.num_kv_heads, hd), dtype),
                "pos": jnp.full((n, num_blocks, block_size), -1, jnp.int32)}

    return {"body": group(n_body), "tail": group(n_tail)}


def _scan_blocks_paged(stacked, cfg, x, pool, table, ring_len, positions,
                       prefill_chunk):
    if stacked is None:
        return x, pool

    def body(x, inp):
        layer_p, layer_pool = inp
        if prefill_chunk:
            h, layer_pool = attn.attention_prefill_paged(
                layer_p["attn"], cfg, norm(cfg, layer_p["ln1"], x),
                layer_pool, table, ring_len, positions)
        else:
            h, layer_pool = attn.attention_decode_paged(
                layer_p["attn"], cfg, norm(cfg, layer_p["ln1"], x),
                layer_pool, table, ring_len, positions)
        x = x + h
        if cfg.num_experts:
            h, _ = moe.moe_apply_dense(layer_p["moe"], cfg,
                                       norm(cfg, layer_p["ln2"], x))
        else:
            h = mlp(layer_p["mlp"], norm(cfg, layer_p["ln2"], x), cfg.mlp_act)
        return x + h, layer_pool

    n = jax.tree.leaves(stacked)[0].shape[0]
    x, pool = jax.lax.scan(body, x, (stacked, pool),
                           unroll=n if cfg.unroll_layers else 1)
    return x, pool


def decode_step_paged(params, cfg, token, position, pool, table, ring_len):
    """One decode step against the shared block pool. token/position: (B,);
    table: (B, mb) block ids (0 = unmapped); ring_len: (B,) logical ring
    modulus per request. Returns (logits (B, V), pool)."""
    x = embedding(params["embed"], token[:, None])
    x, body_p = _scan_blocks_paged(params["body"], cfg, x, pool["body"],
                                   table, ring_len, position, False)
    x, tail_p = _scan_blocks_paged(params["tail"], cfg, x, pool["tail"],
                                   table, ring_len, position, False)
    x = norm(cfg, params["final_norm"], x)
    logits = dense(params["lm_head"], x)[:, 0]
    return logits, {"body": body_p, "tail": tail_p}


def prefill_paged(params, cfg, tokens, positions, pool, table, ring_len):
    """Chunked prefill against the shared block pool. tokens/positions:
    (B, c). Returns (logits (B, c, V), pool)."""
    x = embedding(params["embed"], tokens)
    x, body_p = _scan_blocks_paged(params["body"], cfg, x, pool["body"],
                                   table, ring_len, positions, True)
    x, tail_p = _scan_blocks_paged(params["tail"], cfg, x, pool["tail"],
                                   table, ring_len, positions, True)
    x = norm(cfg, params["final_norm"], x)
    logits = dense(params["lm_head"], x)
    return logits, {"body": body_p, "tail": tail_p}
