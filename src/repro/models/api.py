"""Unified model API: every architecture exposes the same surface.

    model = build_model(cfg)
    params = model.init(key)
    loss   = model.loss(params, batch)            # train / FL local step
    logits, aux = model.forward(params, batch)    # full-seq (prefill)
    logits, cache = model.decode_step(params, token, position, cache)
    mask   = model.fes_mask(params)               # paper Eq.(2) split: True = classifier
    cp     = model.compute_copy(params)           # master weights -> compute dtype

``input_specs`` builds ShapeDtypeStruct stand-ins for the multi-pod dry-run
(no allocation). Modality frontends (audio conv codec, ViT) are stubs per
the assignment: specs hand the backbone precomputed frame/patch embeddings.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import cnn, encdec, transformer


# Top-level param keys that constitute the paper's "classifier" (omega^c).
CLASSIFIER_KEYS = ("tail", "final_norm", "lm_head", "fc1", "fc2", "fc3")


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[Any], Any]
    loss: Callable[[Any, Any], jax.Array]
    forward: Callable[[Any, Any], Any]
    decode_step: Callable[..., Any] | None
    init_decode_cache: Callable[..., Any] | None
    #: last-position logits over a full padded batch (dry-run costing)
    prefill_logits: Callable[[Any, Any], Any] | None = None
    #: chunked prefill(params, tokens, positions, cache) -> (logits, cache);
    #: bit-identical to looping decode_step (None = per-token only family)
    prefill: Callable[..., Any] | None = None
    #: paged-KV serving surface (attention families only)
    init_paged_pool: Callable[..., Any] | None = None
    decode_step_paged: Callable[..., Any] | None = None
    prefill_paged: Callable[..., Any] | None = None

    def compute_copy(self, params):
        """The weights in the compute dtype (``cfg.dtype``) where the
        config keeps masters in another (``cfg.param_dtype``); the
        client plane differentiates this copy, so gradients take the
        compute dtype's bytes; the serving engines serve it. Where the
        two agree, ``params`` itself (no op traced)."""
        cfg = self.cfg
        if not cfg.param_dtype or cfg.param_dtype == cfg.dtype:
            return params
        dt = jnp.dtype(cfg.dtype)
        return jax.tree.map(
            lambda p: p.astype(dt) if jnp.issubdtype(p.dtype, jnp.floating)
            else p, params)

    def fes_mask(self, params):
        """True leaves = trainable under FES (the classifier omega^c)."""
        return {
            k: jax.tree.map(lambda _: k in CLASSIFIER_KEYS, v)
            for k, v in params.items()
        }


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "cnn":
        return Model(
            cfg=cfg,
            init=lambda key: cnn.init_params(cfg, key),
            loss=lambda p, b: cnn.loss_fn(p, cfg, b),
            forward=lambda p, b: cnn.forward(p, cfg, b),
            decode_step=None,
            init_decode_cache=None,
        )
    if cfg.family == "audio":
        return Model(
            cfg=cfg,
            init=lambda key: encdec.init_params(cfg, key),
            loss=lambda p, b: encdec.loss_fn(p, cfg, b),
            forward=lambda p, b: encdec.forward(p, cfg, b),
            decode_step=lambda p, tok, pos, cache: encdec.decode_step(
                p, cfg, tok, pos, cache),
            init_decode_cache=lambda p, frame_emb, max_len: encdec.init_decode_cache(
                p, cfg, frame_emb, max_len),
            prefill_logits=lambda p, b: encdec.prefill_logits(p, cfg, b),
            prefill=lambda p, toks, pos, cache: encdec.prefill(
                p, cfg, toks, pos, cache),
        )
    # ssm/hybrid decode through recurrent state, not a KV ring: chunked
    # prefill and the paged pool only apply to the attention families.
    attn_family = cfg.family in ("dense", "moe", "vlm")
    return Model(
        cfg=cfg,
        init=lambda key: transformer.init_params(cfg, key),
        loss=lambda p, b: transformer.loss_fn(p, cfg, b),
        forward=lambda p, b: transformer.forward(p, cfg, b),
        decode_step=lambda p, tok, pos, cache: transformer.decode_step(
            p, cfg, tok, pos, cache),
        init_decode_cache=lambda p, batch, max_len: transformer.init_decode_cache(
            cfg, batch, max_len),
        prefill_logits=lambda p, b: transformer.prefill_logits(p, cfg, b),
        prefill=(lambda p, toks, pos, cache: transformer.prefill(
            p, cfg, toks, pos, cache)) if attn_family else None,
        init_paged_pool=(lambda nb, bs: transformer.init_paged_pool(
            cfg, nb, bs)) if attn_family else None,
        decode_step_paged=(lambda p, tok, pos, pool, table, lw:
                           transformer.decode_step_paged(
                               p, cfg, tok, pos, pool, table, lw))
        if attn_family else None,
        prefill_paged=(lambda p, toks, pos, pool, table, lw:
                       transformer.prefill_paged(
                           p, cfg, toks, pos, pool, table, lw))
        if attn_family else None,
    )


# --------------------------------------------------------- input specs -----

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for a (arch x input-shape) pair.

    train/prefill -> {"batch": {...}}
    decode        -> {"token", "position", "cache"} (cache built structurally
                     via eval_shape so no memory is touched).
    """
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)

    if cfg.family == "cnn":
        return {"batch": {"image": _sds((B, 28, 28, 1), jnp.float32),
                          "label": _sds((B,), jnp.int32)}}

    if shape.kind in ("train", "prefill"):
        batch = {"tokens": _sds((B, S), jnp.int32)}
        if cfg.family == "vlm":
            batch["patch_emb"] = _sds(
                (B, cfg.num_patches, cfg.vision_dim or cfg.d_model), dt)
        if cfg.family == "audio":
            batch["frame_emb"] = _sds((B, cfg.encoder_seq, cfg.d_model), dt)
        return {"batch": batch}

    # decode: one new token against a seq_len-sized KV cache/state
    token = _sds((B,), jnp.int32)
    position = _sds((B,), jnp.int32)
    if cfg.family == "audio":
        params_shape = jax.eval_shape(
            lambda k: encdec.init_params(cfg, k), jax.random.PRNGKey(0))
        frame_sds = _sds((B, cfg.encoder_seq, cfg.d_model), dt)
        cache = jax.eval_shape(
            lambda p, f: encdec.init_decode_cache(p, cfg, f, S),
            params_shape, frame_sds)
    else:
        cache = jax.eval_shape(
            lambda: transformer.init_decode_cache(cfg, B, S))
    return {"token": token, "position": position, "cache": cache}
