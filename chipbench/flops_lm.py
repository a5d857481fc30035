"""Operations a decoder-only language model's federated round requires,
from shapes alone, by ``chipbench/flops.py``'s rules: a multiply-add
counts two operations; training costs the forward pass plus a backward
pass of twice the forward; a client limited by FES (paper Eq. 3) needs
the forward pass of the whole model and the backward pass of the
classifier only; causal attention counts half of its score and value
products; recomputation chosen to save memory is not counted, nor the
embedding lookup (a gather).

``cfg`` is a configuration file of ``chipbench/configs`` (the keys of
``minitron-8b-4l.json``).
"""
from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    """Weights one block multiplies by: the q, k, v and output
    projections and the MLP (two matrices; three where gated)."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    q, kv = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    mlp = (3 if cfg.get("mlp_act") == "swiglu" else 2) * d * cfg["d_ff"]
    return 2 * d * q + 2 * d * kv + mlp


def attention_forward(cfg: dict, seq: int) -> float:
    """One block's causal attention over one sequence: the score and
    value products, 2 x (2 S^2 H hd), halved by the causal mask."""
    return 2.0 * seq * seq * cfg["num_heads"] * cfg["head_dim"]


def forward(cfg: dict, seq: int) -> tuple[float, float]:
    """(feature extractor, classifier) forward FLOPs of one sequence."""
    n_tail = min(cfg["fes_tail_layers"], cfg["num_layers"])
    per_layer = 2.0 * seq * layer_matmul_params(cfg) + attention_forward(
        cfg, seq)
    head = 2.0 * seq * cfg["d_model"] * cfg["vocab_size"]
    return ((cfg["num_layers"] - n_tail) * per_layer,
            n_tail * per_layer + head)


def round_flops(cfg: dict, steps: int, batch: int, seq: int,
                limited: list) -> float:
    """FLOPs one round requires: every silo's ``steps`` local steps of
    ``batch`` sequences of ``seq`` tokens (``limited`` per silo)."""
    body, clf = forward(cfg, seq)
    fwd = body + clf
    return steps * batch * sum(fwd + 2.0 * (clf if lim else fwd)
                               for lim in limited)
