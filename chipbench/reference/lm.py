"""Plain float32 reference of the Minitron (Nemotron-4) decoder block:
forward pass, next-token loss and its gradient, in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No scan, no remat, no
kernels, nothing from the program under test.

The block, as the published ``NemotronForCausalLM`` computes it:

    h = LN1p(x);  q, k, v = h Wq, h Wk, h Wv     (48 x 128 q, 8 x 128 kv)
    RoPE (theta 10000) on the leading rotary_frac of each head's dims
    x = x + causal_softmax(q k^T / sqrt(128)) v Wo   (query head i reads
                                                      kv head i // 6)
    x = x + relu(LN1p(x) W_in)^2 W_out
    logits = LN1p_final(x) W_head
    LN1p(x) = (x - mean) / sqrt(var + 1e-5) * (g + 1) + b

Departures from the published description, each forced by one chip a
silo: ``num_layers`` 4 of 32 and ``vocab_size`` a 32,000-id slice of
256,000 (the loss is a softmax over the slice alone); the weights are
seeded (``param_specs``), not the published checkpoint. The attention
of one sequence and one KV head is a ``custom_vjp`` whose backward
recomputes the softmax from the saved log-sum-exp (the textbook
gradient): the saved probabilities of a 2 x 2048-token step would not
fit beside the f32 weights and gradients.

Parameters follow the program's tree (layers stacked on a leading axis,
split at the FES boundary into ``body`` and ``tail``) so that one set of
seeded arrays feeds both; this module reads layer ``l`` as row ``l`` of
that stack.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: top-level keys of the FES classifier (paper Eq. 2): the tail blocks,
#: the final norm and the output head; the rest is the feature extractor
CLASSIFIER = ("tail", "final_norm", "lm_head")


def _block_specs(c: dict, n: int, dtype) -> dict:
    d, hd = c["d_model"], c["head_dim"]
    qw, kw, ff = c["num_heads"] * hd, c["num_kv_heads"] * hd, c["d_ff"]

    def lin(a, b):
        return ((n, a, b), "uniform", a ** -0.5, dtype)

    def ln():
        return {"g": ((n, d), "zeros", 0.0, dtype),
                "b": ((n, d), "zeros", 0.0, dtype)}

    return {"ln1": ln(), "ln2": ln(),
            "attn": {"wq": {"w": lin(d, qw)}, "wk": {"w": lin(d, kw)},
                     "wv": {"w": lin(d, kw)}, "wo": {"w": lin(qw, d)}},
            "mlp": {"w_in": {"w": lin(d, ff)}, "w_out": {"w": lin(ff, d)}}}


def param_specs(c: dict, dtype=F32) -> dict:
    """Seeded-weight specs (``chipbench.gen.weights``): linear weights
    uniform(+-1/sqrt(fan_in)), the embedding normal(0, 0.02), LayerNorm1p
    weights and biases 0 (an applied scale of 1)."""
    d, v = c["d_model"], c["vocab_size"]
    n_tail = min(c["fes_tail_layers"], c["num_layers"])
    return {"embed": {"table": ((v, d), "normal", 0.02, dtype)},
            "body": _block_specs(c, c["num_layers"] - n_tail, dtype),
            "tail": _block_specs(c, n_tail, dtype),
            "final_norm": {"g": ((d,), "zeros", 0.0, dtype),
                           "b": ((d,), "zeros", 0.0, dtype)},
            "lm_head": {"w": ((d, v), "uniform", d ** -0.5, dtype)}}


def layers(params) -> list:
    """The blocks' weights in depth order."""
    out = []
    for group in ("body", "tail"):
        n = jax.tree.leaves(params[group])[0].shape[0]
        out += [jax.tree.map(lambda a, i=i: a[i], params[group])
                for i in range(n)]
    return out


def layernorm1p(p, x, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (p["g"] + 1.0) + p["b"]


def rope(x, rotary_dim: int, theta: float):
    """x (S, heads, hd): rotate the leading ``rotary_dim`` dims of each
    head by position, halves paired as in ``rotate_half``."""
    S = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=F32)
                          / rotary_dim)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = rotary_dim // 2
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rotary_dim:]], axis=-1)


def _scores(q, k):
    """q (S, R, hd), k (S, hd) -> causal scores (R, S, S)."""
    S = q.shape[0]
    s = jnp.einsum("qrd,kd->rqk", q, k) * q.shape[-1] ** -0.5
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)


def _group_fwd(q, k, v):
    s = _scores(q, k)
    lse = jax.nn.logsumexp(s, axis=-1)
    o = jnp.einsum("rqk,kd->qrd", jnp.exp(s - lse[..., None]), v)
    return o, (q, k, v, o, lse)


def _group_bwd(res, do):
    q, k, v, o, lse = res
    p = jnp.exp(_scores(q, k) - lse[..., None])
    dv = jnp.einsum("rqk,qrd->kd", p, do)
    dp = jnp.einsum("qrd,kd->rqk", do, v)
    delta = jnp.einsum("qrd,qrd->rq", do, o)
    ds = p * (dp - delta[..., None]) * q.shape[-1] ** -0.5
    return (jnp.einsum("rqk,kd->qrd", ds, k),
            jnp.einsum("rqk,qrd->kd", ds, q), dv)


@jax.custom_vjp
def head_group_attention(q, k, v):
    """Causal attention of the R query heads that read one KV head:
    q (S, R, hd), k and v (S, hd) -> (S, R, hd)."""
    return _group_fwd(q, k, v)[0]


head_group_attention.defvjp(_group_fwd, _group_bwd)


def block(p, c: dict, x):
    """One decoder block on one sequence, x (S, d)."""
    S = x.shape[0]
    hd, H, KH = c["head_dim"], c["num_heads"], c["num_kv_heads"]
    R = H // KH
    h = layernorm1p(p["ln1"], x)
    q = (h @ p["attn"]["wq"]["w"]).reshape(S, H, hd)
    k = (h @ p["attn"]["wk"]["w"]).reshape(S, KH, hd)
    v = (h @ p["attn"]["wv"]["w"]).reshape(S, KH, hd)
    rd = 2 * int(hd * c["rotary_frac"] / 2)
    q, k = rope(q, rd, c["rope_theta"]), rope(k, rd, c["rope_theta"])
    o = jax.vmap(head_group_attention, in_axes=1, out_axes=1)(
        q.reshape(S, KH, R, hd), k, v)
    x = x + o.reshape(S, H * hd) @ p["attn"]["wo"]["w"]
    u = layernorm1p(p["ln2"], x) @ p["mlp"]["w_in"]["w"]
    return x + jnp.square(jax.nn.relu(u)) @ p["mlp"]["w_out"]["w"]


def logits(params, c: dict, tokens):
    """tokens (S,) -> (S, vocab) f32."""
    x = params["embed"]["table"][tokens]
    for lp in layers(params):
        x = block(lp, c, x)
    return layernorm1p(params["final_norm"], x) @ params["lm_head"]["w"]


def loss(params, c: dict, tokens, shift=1, seqs=None):
    """Mean next-token cross entropy over a batch, tokens (B, S): each
    position predicts the token ``shift`` after it (1: the next one;
    another value, which may be traced, is a planted fault). Positions
    with no token that far ahead are left out. Only the first ``seqs``
    sequences count (all by default; fewer, which may be traced, is a
    planted fault)."""
    B, S = tokens.shape
    seqs = B if seqs is None else seqs
    keep = (jnp.arange(S) < S - shift).astype(F32)
    total = 0.0
    for b in range(B):
        z = logits(params, c, tokens[b]).astype(F32)
        y = jnp.roll(tokens[b], -shift)
        nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, y[:, None], axis=-1)[:, 0]
        total = total + jnp.where(b < seqs, 1.0, 0.0) * jnp.sum(nll * keep)
    return total / (jnp.asarray(seqs, F32) * jnp.sum(keep))


def cast(params, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), params)


def sgd_step(params, c: dict, tokens, lr, limited, *, dtype=F32,
             shift=1, bf16_carry=False, seqs=None):
    """One local SGD step on a batch of sequences (B, S): -> (new
    weights, the step's loss, taken before it). ``limited`` (a traced
    bool) is the FES mask: the feature extractor's gradient is zero, so
    only the classifier moves. ``dtype`` is the arithmetic's precision
    (f32; the control passes bfloat16 weights and bfloat16). ``shift``,
    ``bf16_carry`` and ``seqs`` (all may be traced) plant faults:
    targets ``shift`` tokens ahead, the stepped weights rounded to
    bfloat16 before they are kept, and only the first ``seqs``
    sequences of the batch trained on."""
    with jax.default_matmul_precision("highest"):
        val, g = jax.value_and_grad(
            lambda p: loss(cast(p, dtype), c, tokens, shift, seqs))(params)

    def update(path, p, gi):
        if path[0].key not in CLASSIFIER:
            gi = jnp.where(limited, jnp.zeros_like(gi), gi)
        new = (p - lr * gi).astype(p.dtype)
        # an explicit rounding to bfloat16's 8 mantissa bits, which the
        # compiler keeps where it would drop an f32-bf16-f32 convert pair
        rounded = jax.lax.reduce_precision(new, exponent_bits=8,
                                           mantissa_bits=7)
        return jnp.where(bf16_carry, rounded, new)

    return jax.tree_util.tree_map_with_path(update, params, g), val
