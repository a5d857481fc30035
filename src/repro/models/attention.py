"""Attention: GQA + RoPE (on a leading share of each head's dims,
``cfg.rotary_dim``) + optional sliding window. The query width is
``num_heads * head_dim``, which need not equal ``d_model``.

Every model path, on a TPU too, runs ``chunked_attention``: an
XLA-native online-softmax over KV chunks (lax.scan), O(S * chunk)
transient memory, compiling on any backend; this is what the multi-pod
dry-run lowers. ``kernels.ref.flash_attention_ref`` is its plain
softmax reference.

Decode uses a KV cache; sliding-window archs use a ring-buffer cache of
size ``window`` so the long_500k cache is O(window), not O(S).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense, dense_init

NEG_INF = -1e30


def attn_init(key, cfg, dtype):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.num_heads * hd, dtype, cfg.qkv_bias),
        "wk": dense_init(ks[1], cfg.d_model, cfg.num_kv_heads * hd, dtype, cfg.qkv_bias),
        "wv": dense_init(ks[2], cfg.d_model, cfg.num_kv_heads * hd, dtype, cfg.qkv_bias),
        "wo": dense_init(ks[3], cfg.num_heads * hd, cfg.d_model, dtype),
    }


def _split_heads(x, n_heads, hd):
    return x.reshape(*x.shape[:-1], n_heads, hd)


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def chunked_attention(q, k, v, q_positions, kv_positions, *, causal: bool,
                      window: int = 0, chunk: int = 512, unroll: bool = False):
    """Online-softmax attention, blocked over (q-block x kv-chunk).

    q: (B, Sq, H, hd); k/v: (B, Skv, H, hd) (kv already repeated to H heads).
    positions: (B, Sq) / (B, Skv) absolute positions (for masking).

    When queries and keys cover the SAME aligned range (self-attention,
    train/prefill), fully-masked kv chunks are skipped STRUCTURALLY: each
    q-block only visits kv chunks inside its causal frontier and sliding
    window — 2x FLOP saving for causal, ~S/window for SWA (§Perf H1-it3).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    chunk = min(chunk, Skv)
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=2**30)
    n_chunks = k.shape[1] // chunk
    scale = hd ** -0.5
    qf = (q * scale).astype(jnp.float32)

    kc = k.reshape(B, n_chunks, chunk, H, hd)
    vc = v.reshape(B, n_chunks, chunk, H, hd)
    pc = kv_positions.reshape(B, n_chunks, chunk)

    def make_step(qb, q_pos_b):
        """Online-softmax update for one (q-block, kv-chunk) pair."""
        def step(carry, inp):
            m, l, acc = carry           # (B,H,qb), (B,H,qb), (B,H,qb,hd)
            kb, vb, pb = inp            # (B,chunk,H,hd), ..., (B,chunk)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb.astype(jnp.float32))
            # padded KV slots carry position 2**30: always masked out
            valid = (pb < 2**29)[:, None, None, :]
            mask = jnp.logical_and(
                valid,
                pb[:, None, None, :] <= q_pos_b[:, None, :, None]
                if causal else True)
            if window:
                mask = jnp.logical_and(
                    mask, pb[:, None, None, :]
                    > q_pos_b[:, None, :, None] - window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None
        return step

    def run_range(qb, q_pos_b, k_lo, k_hi):
        """Online softmax of one q block over kv chunks [k_lo, k_hi)."""
        nb = qb.shape[1]
        m0 = jnp.full((B, H, nb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, nb), jnp.float32)
        a0 = jnp.zeros((B, H, nb, hd), jnp.float32)
        xs = (jnp.moveaxis(kc[:, k_lo:k_hi], 1, 0),
              jnp.moveaxis(vc[:, k_lo:k_hi], 1, 0),
              jnp.moveaxis(pc[:, k_lo:k_hi], 1, 0))
        (m, l, acc), _ = jax.lax.scan(
            make_step(qb, q_pos_b), (m0, l0, a0), xs,
            unroll=(k_hi - k_lo) if unroll else 1)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 1, 2)                 # (B, nb, H, hd)

    # structural chunk skipping needs statically-aligned self-attention
    aligned = causal and Sq == Skv and Sq % chunk == 0
    if not aligned:
        return run_range(qf, q_positions, 0, n_chunks).astype(q.dtype)

    n_q = Sq // chunk
    outs = []
    for qi in range(n_q):
        sl = slice(qi * chunk, (qi + 1) * chunk)
        hi = qi + 1                                    # causal frontier
        lo = max(0, (qi * chunk - window) // chunk) if window else 0
        outs.append(run_range(qf[:, sl], q_positions[:, sl], lo, hi))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def attention_fwd(p, cfg, x, positions, *, causal=True, kv_x=None,
                  kv_positions=None, window=None):
    """Full-sequence attention (train / prefill / encoder / cross).

    kv_x: source of K/V (cross-attention) — defaults to x (self-attention).
    """
    hd = cfg.resolved_head_dim
    n_rep = cfg.num_heads // cfg.num_kv_heads
    kv_src = x if kv_x is None else kv_x
    kv_pos = positions if kv_positions is None else kv_positions
    q = _split_heads(dense(p["wq"], x), cfg.num_heads, hd)
    k = _split_heads(dense(p["wk"], kv_src), cfg.num_kv_heads, hd)
    v = _split_heads(dense(p["wv"], kv_src), cfg.num_kv_heads, hd)
    if causal or kv_x is None:           # rope only for self-attention
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
        k = apply_rope(k, kv_pos, cfg.rope_theta, cfg.rotary_dim)
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    w = cfg.sliding_window if window is None else window
    out = chunked_attention(q, k, v, positions, kv_pos, causal=causal, window=w,
                            chunk=cfg.attn_chunk, unroll=cfg.unroll_chunks)
    return dense(p["wo"], out.reshape(*x.shape[:-1], cfg.num_heads * hd))


# ------------------------------------------------------------- decoding ----

def _decode_rows(q, kk, vv, mask):
    """One query row q (B, 1, H, hd) attending over kk, vv (B, Skv, H, hd)
    where mask (B, Skv) holds -> (B, 1, H, hd). The row is doubled so that
    both products are matrix products, summed in the order the chunked
    prefill's are (a dot with one row may lower to a matrix-vector
    product that sums in another order): decode and prefill then stay
    bitwise equal."""
    s = jnp.einsum("bqhd,bkhd->bhqk", jnp.concatenate([q, q], axis=1), kk)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", a, vv)[:, :1]


def _ring_values(a, v):
    """Weights a (B, H, Sq, Skv) over each query row's own ring v (B, Sq,
    Skv, H, hd) -> (B, Sq, H, hd), each row doubled as in
    ``_decode_rows`` so that it sums in the decode's order."""
    out = jnp.einsum("bhqrk,bqkhd->bqrhd", jnp.stack([a, a], axis=3), v)
    return out[:, :, 0]


def init_kv_cache(cfg, batch, max_len, dtype):
    """Ring-buffer cache when sliding_window > 0, else linear cache."""
    hd = cfg.resolved_head_dim
    L = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    return {
        "k": jnp.zeros((batch, L, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, L, cfg.num_kv_heads, hd), dtype),
        "pos": jnp.full((batch, L), -1, jnp.int32),   # absolute positions held
    }


def attention_decode(p, cfg, x, cache, position):
    """One-token decode. x: (B, 1, d); position: (B,) absolute index."""
    hd = cfg.resolved_head_dim
    n_rep = cfg.num_heads // cfg.num_kv_heads
    B = x.shape[0]
    q = _split_heads(dense(p["wq"], x), cfg.num_heads, hd)
    k = _split_heads(dense(p["wk"], x), cfg.num_kv_heads, hd)
    v = _split_heads(dense(p["wv"], x), cfg.num_kv_heads, hd)
    q = apply_rope(q, position[:, None], cfg.rope_theta, cfg.rotary_dim)
    k = apply_rope(k, position[:, None], cfg.rope_theta, cfg.rotary_dim)

    L = cache["k"].shape[1]
    slot = (position % L).astype(jnp.int32)            # ring slot
    bidx = jnp.arange(B)
    new_k = cache["k"].at[bidx, slot].set(k[:, 0])
    new_v = cache["v"].at[bidx, slot].set(v[:, 0])
    new_pos = cache["pos"].at[bidx, slot].set(position)
    cache = {"k": new_k, "v": new_v, "pos": new_pos}

    kk = _repeat_kv(cache["k"], n_rep).astype(jnp.float32)
    vv = _repeat_kv(cache["v"], n_rep).astype(jnp.float32)
    valid = cache["pos"] >= 0
    mask = jnp.logical_and(valid, cache["pos"] <= position[:, None])
    if cfg.sliding_window:
        mask = jnp.logical_and(
            mask, cache["pos"] > position[:, None] - cfg.sliding_window)
    out = _decode_rows((q * hd**-0.5).astype(jnp.float32), kk, vv,
                       mask).astype(x.dtype)
    out = out.reshape(B, 1, cfg.num_heads * hd)
    return dense(p["wo"], out), cache


def cross_attention_decode(p, cfg, x, enc_k, enc_v):
    """Cross-attention against precomputed encoder K/V.

    enc_k/enc_v: (B, S_enc, KH, hd) — computed once at the start of decode.
    x: (B, S, d) — S = 1 at decode time, a whole prompt chunk at prefill.
    """
    hd = cfg.resolved_head_dim
    n_rep = cfg.num_heads // cfg.num_kv_heads
    B, S, _ = x.shape
    q = _split_heads(dense(p["wq"], x), cfg.num_heads, hd)
    kk = _repeat_kv(enc_k, n_rep).astype(jnp.float32)
    vv = _repeat_kv(enc_v, n_rep).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", (q * hd**-0.5).astype(jnp.float32), kk)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", a, vv).astype(x.dtype)
    return dense(p["wo"], out.reshape(B, S, cfg.num_heads * hd))


# ----------------------------------------------------- chunked prefill -----

#: pad sentinel on the query/position axis of a prefill chunk: rows with
#: position >= PAD_FLOOR are padding — they never enter the cache and
#: their outputs are garbage the caller must drop (same convention as
#: chunked_attention's padded KV slots).
PAD_FLOOR = 2**29
PAD_POS = 2**30


def _chunk_slots(positions, ring_len):
    """Cache slots for one prefill chunk: consecutive from the chunk's
    FIRST position (which is always real), so pad rows land on distinct
    no-op slots instead of `PAD_POS % ring_len` colliding with a real
    write. Requires chunk <= ring_len (engine contract)."""
    c = positions.shape[1]
    return ((positions[:, :1] + jnp.arange(c, dtype=jnp.int32))
            % ring_len).astype(jnp.int32)


def attention_prefill(p, cfg, x, cache, positions):
    """Blockwise prefill of one prompt chunk against the decode cache.

    x: (B, c, d); positions: (B, c) absolute, consecutive from the
    chunk's first position; pad rows carry position >= PAD_FLOOR.

    BIT-IDENTITY CONTRACT (gated in tests/test_serve_plane.py): logits
    and cache leaves match the per-token ``attention_decode`` loop
    bitwise.
      * linear cache (window == 0): the whole chunk's K/V is written
        first; slots at future positions are masked to NEG_INF, whose
        softmax weight is exactly 0.0, so every query row reproduces
        the decode-time score vector elementwise.
      * ring cache (window > 0): a batched write evicts history that
        earlier in-chunk queries still need, so scores/values are
        SELECTED per query between the pre-write and post-write cache
        states — exactly the ring state the per-token path sees at each
        position. Transient memory is O(c * ring * H * hd) — the
        blockwise-prefill memory bound; requires c <= ring length.
    """
    hd = cfg.resolved_head_dim
    n_rep = cfg.num_heads // cfg.num_kv_heads
    B, c, _ = x.shape
    q = _split_heads(dense(p["wq"], x), cfg.num_heads, hd)
    k = _split_heads(dense(p["wk"], x), cfg.num_kv_heads, hd)
    v = _split_heads(dense(p["wv"], x), cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_dim)

    L = cache["k"].shape[1]
    slots = _chunk_slots(positions, L)
    bidx = jnp.arange(B)[:, None]
    real = positions < PAD_FLOOR
    # pad rows write their slot's CURRENT entry back (a no-op write);
    # in-chunk slots are distinct, so no real write is clobbered
    k_w = jnp.where(real[..., None, None], k, cache["k"][bidx, slots])
    v_w = jnp.where(real[..., None, None], v, cache["v"][bidx, slots])
    p_w = jnp.where(real, positions, cache["pos"][bidx, slots])
    new = {"k": cache["k"].at[bidx, slots].set(k_w),
           "v": cache["v"].at[bidx, slots].set(v_w),
           "pos": cache["pos"].at[bidx, slots].set(p_w)}

    qf = (q * hd**-0.5).astype(jnp.float32)
    kk = _repeat_kv(new["k"], n_rep).astype(jnp.float32)
    vv = _repeat_kv(new["v"], n_rep).astype(jnp.float32)
    if not cfg.sliding_window:
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kk)
        mask = jnp.logical_and(new["pos"][:, None, :] >= 0,
                               new["pos"][:, None, :] <= positions[..., None])
        s = jnp.where(mask[:, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", a, vv).astype(x.dtype)
    else:
        kk_old = _repeat_kv(cache["k"], n_rep).astype(jnp.float32)
        vv_old = _repeat_kv(cache["v"], n_rep).astype(jnp.float32)
        s_new = jnp.einsum("bqhd,bkhd->bhqk", qf, kk)
        s_old = jnp.einsum("bqhd,bkhd->bhqk", qf, kk_old)
        # written[t, s]: slot s's in-chunk write happened at position <= t
        # (untouched slots keep new == old, so either branch is fine)
        written = jnp.logical_and(
            new["pos"][:, None, :] != cache["pos"][:, None, :],
            new["pos"][:, None, :] <= positions[..., None])
        pos_eff = jnp.where(written, new["pos"][:, None, :],
                            cache["pos"][:, None, :])
        s = jnp.where(written[:, None], s_new, s_old)
        mask = jnp.logical_and(pos_eff >= 0, pos_eff <= positions[..., None])
        mask = jnp.logical_and(
            mask, pos_eff > positions[..., None] - cfg.sliding_window)
        s = jnp.where(mask[:, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1)
        v_eff = jnp.where(written[..., None, None],
                          vv[:, None], vv_old[:, None])
        out = _ring_values(a, v_eff).astype(x.dtype)
    out = out.reshape(B, c, cfg.num_heads * hd)
    return dense(p["wo"], out), new


# ----------------------------------------------------------- paged KV ------

def paged_view(pool, table):
    """Dense per-request view of a block pool.

    pool: {"k"/"v": (nb, bs, KH, hd), "pos": (nb, bs)}; table: (B, mb)
    int32 physical block ids per request (0 = the reserved null block).
    Returns (k, v, pos) shaped (B, mb*bs, ...) — the same layout as a
    dense linear/ring cache of length mb*bs, so the attention math (and
    its numerics) is shared with the dense-cache paths.
    """
    nb, bs = pool["pos"].shape
    blk = jnp.clip(table, 0, nb - 1)
    k = pool["k"][blk]                      # (B, mb, bs, KH, hd)
    v = pool["v"][blk]
    pos = jnp.where((table > 0)[..., None], pool["pos"][blk], -1)
    B, mb = table.shape
    return (k.reshape(B, mb * bs, *k.shape[3:]),
            v.reshape(B, mb * bs, *v.shape[3:]),
            pos.reshape(B, mb * bs))


def _paged_write(pool, table, slots, k, v, pos):
    """Scatter per-request logical ring slots into the pool.

    slots: (B, c) logical slots; k/v: (B, c, KH, hd); pos: (B, c).
    Requests own disjoint blocks, so cross-request writes never collide;
    slots within a request's chunk are distinct by the _chunk_slots
    contract. Rows whose table entry is 0 land in the null block.
    """
    nb, bs = pool["pos"].shape
    blk_i = slots // bs
    phys = jnp.clip(jnp.take_along_axis(table, blk_i, axis=1), 0, nb - 1)
    off = slots % bs
    return {"k": pool["k"].at[phys, off].set(k),
            "v": pool["v"].at[phys, off].set(v),
            "pos": pool["pos"].at[phys, off].set(pos)}


def attention_decode_paged(p, cfg, x, pool, table, ring_len, position):
    """One-token decode against the shared block pool.

    x: (B, 1, d); table: (B, mb); ring_len: (B,) per-request logical
    ring modulus (min(max_len, window) for SWA, the request's max_len
    otherwise); position: (B,) absolute. Same math as
    ``attention_decode`` on the gathered dense view.
    """
    hd = cfg.resolved_head_dim
    n_rep = cfg.num_heads // cfg.num_kv_heads
    B = x.shape[0]
    q = _split_heads(dense(p["wq"], x), cfg.num_heads, hd)
    k = _split_heads(dense(p["wk"], x), cfg.num_kv_heads, hd)
    v = _split_heads(dense(p["wv"], x), cfg.num_kv_heads, hd)
    q = apply_rope(q, position[:, None], cfg.rope_theta, cfg.rotary_dim)
    k = apply_rope(k, position[:, None], cfg.rope_theta, cfg.rotary_dim)

    slots = (position % ring_len).astype(jnp.int32)[:, None]
    pool = _paged_write(pool, table, slots, k, v, position[:, None])

    kk, vv, kpos = paged_view(pool, table)
    kk = _repeat_kv(kk, n_rep).astype(jnp.float32)
    vv = _repeat_kv(vv, n_rep).astype(jnp.float32)
    mask = jnp.logical_and(kpos >= 0, kpos <= position[:, None])
    if cfg.sliding_window:
        mask = jnp.logical_and(
            mask, kpos > position[:, None] - cfg.sliding_window)
    out = _decode_rows((q * hd**-0.5).astype(jnp.float32), kk, vv,
                       mask).astype(x.dtype)
    out = out.reshape(B, 1, cfg.num_heads * hd)
    return dense(p["wo"], out), pool


def attention_prefill_paged(p, cfg, x, pool, table, ring_len, positions):
    """Blockwise prefill of one prompt chunk into the shared block pool —
    ``attention_prefill`` with the cache axes living behind a block
    table. Same pad-sentinel / selection semantics; requires
    chunk <= min(ring_len)."""
    hd = cfg.resolved_head_dim
    n_rep = cfg.num_heads // cfg.num_kv_heads
    B, c, _ = x.shape
    q = _split_heads(dense(p["wq"], x), cfg.num_heads, hd)
    k = _split_heads(dense(p["wk"], x), cfg.num_kv_heads, hd)
    v = _split_heads(dense(p["wv"], x), cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_dim)

    nb, bs = pool["pos"].shape
    slots = ((positions[:, :1] + jnp.arange(c, dtype=jnp.int32))
             % ring_len[:, None]).astype(jnp.int32)
    blk_i = slots // bs
    phys = jnp.clip(jnp.take_along_axis(table, blk_i, axis=1), 0, nb - 1)
    off = slots % bs
    real = positions < PAD_FLOOR
    k_w = jnp.where(real[..., None, None], k, pool["k"][phys, off])
    v_w = jnp.where(real[..., None, None], v, pool["v"][phys, off])
    p_w = jnp.where(real, positions, pool["pos"][phys, off])

    old_k, old_v, old_pos = paged_view(pool, table)
    pool = {"k": pool["k"].at[phys, off].set(k_w),
            "v": pool["v"].at[phys, off].set(v_w),
            "pos": pool["pos"].at[phys, off].set(p_w)}
    new_k, new_v, new_pos = paged_view(pool, table)

    qf = (q * hd**-0.5).astype(jnp.float32)
    kk = _repeat_kv(new_k, n_rep).astype(jnp.float32)
    vv = _repeat_kv(new_v, n_rep).astype(jnp.float32)
    if not cfg.sliding_window:
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kk)
        mask = jnp.logical_and(new_pos[:, None, :] >= 0,
                               new_pos[:, None, :] <= positions[..., None])
        s = jnp.where(mask[:, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", a, vv).astype(x.dtype)
    else:
        kk_old = _repeat_kv(old_k, n_rep).astype(jnp.float32)
        vv_old = _repeat_kv(old_v, n_rep).astype(jnp.float32)
        s_new = jnp.einsum("bqhd,bkhd->bhqk", qf, kk)
        s_old = jnp.einsum("bqhd,bkhd->bhqk", qf, kk_old)
        written = jnp.logical_and(
            new_pos[:, None, :] != old_pos[:, None, :],
            new_pos[:, None, :] <= positions[..., None])
        pos_eff = jnp.where(written, new_pos[:, None, :],
                            old_pos[:, None, :])
        s = jnp.where(written[:, None], s_new, s_old)
        mask = jnp.logical_and(pos_eff >= 0, pos_eff <= positions[..., None])
        mask = jnp.logical_and(
            mask, pos_eff > positions[..., None] - cfg.sliding_window)
        s = jnp.where(mask[:, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1)
        v_eff = jnp.where(written[..., None, None],
                          vv[:, None], vv_old[:, None])
        out = _ring_values(a, v_eff).astype(x.dtype)
    out = out.reshape(B, c, cfg.num_heads * hd)
    return dense(p["wo"], out), pool
