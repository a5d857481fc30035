"""Operations and bytes that the algorithm requires, from shapes alone.

A multiply-add counts two operations. Training a layer costs its
forward pass plus a backward pass of twice the forward (gradients of the
activations and of the weights). A client limited by FES (paper Eq. 3)
needs the forward pass of the whole model and the backward pass of the
classifier only. Evaluation is one forward pass. Recomputation chosen to
save memory is not counted, and causal attention counts half of the
score and value products.
"""
from __future__ import annotations


def cnn_forward(cfg: dict) -> tuple[float, float]:
    """(feature extractor, classifier) forward FLOPs per sample."""
    s = cfg["sizes"]
    h, w, c = s["image_shape"]
    k = s["kernel"]
    body = 0.0
    for c_out in s["conv_channels"]:
        h, w = h - k + 1, w - k + 1                  # VALID convolution
        body += 2.0 * h * w * c_out * k * k * c
        h, w, c = h // 2, w // 2, c_out              # 2x2 max pooling
    fc = s["fc"]
    clf = sum(2.0 * a * b for a, b in zip(fc[:-1], fc[1:]))
    return body, clf


def cnn_round(cfg: dict, steps: int, batch: int, limited: list,
              n_eval: int) -> float:
    """FLOPs one federated round of the CNN requires: every selected
    client's local steps (``limited`` per client), and the evaluation of
    ``n_eval`` test samples."""
    body, clf = cnn_forward(cfg)
    fwd = body + clf
    per_sample = [fwd + 2.0 * (clf if lim else fwd) for lim in limited]
    return steps * batch * sum(per_sample) + n_eval * fwd
