"""MNIST-shaped synthetic images (28x28x1, ten classes) from a seed.

The same recipe as the program's ``data/synth.make_image_classification``
(a smooth random template per class, a per-sample circular shift of up
to two pixels, a per-sample contrast factor and Gaussian noise), drawn
with numpy's ``default_rng`` and vectorised, so sixty thousand images
take well under a second and any seed of any size is accepted. The
program gets only the arrays.

Every class holds the same number of images (MNIST's classes hold
nearly the same), in an order drawn from the seed. So every seed gives
the same client sizes under ``shard_partition``, the same local steps a
round and the same bytes to stage: the seed changes which images, not
how much work.
"""
from __future__ import annotations

import numpy as np

SIDE = 28
BLOCK = 10_000          # images generated per block (bounds host memory)


def _templates(rng, n_classes: int) -> np.ndarray:
    xs = np.linspace(0.0, 1.0, SIDE)
    xx, yy = np.meshgrid(xs, xs)
    out = np.zeros((n_classes, SIDE, SIDE))
    for c in range(n_classes):
        for _ in range(4):
            fx, fy = rng.integers(1, 5, size=2)
            ph = rng.random(2) * 2 * np.pi
            out[c] += (rng.standard_normal()
                       * np.sin(2 * np.pi * fx * xx + ph[0])
                       * np.sin(2 * np.pi * fy * yy + ph[1]))
        out[c] /= np.abs(out[c]).max()
    return out.astype(np.float32)


def _draw(rng, templates: np.ndarray, n: int) -> dict:
    n_classes = templates.shape[0]
    if n % n_classes:
        raise ValueError(f"{n} images do not split evenly into "
                         f"{n_classes} classes")
    labels = rng.permutation(np.repeat(np.arange(n_classes, dtype=np.int32),
                                       n // n_classes))
    images = np.empty((n, SIDE, SIDE, 1), np.float32)
    ar = np.arange(SIDE)
    for s in range(0, n, BLOCK):
        lab = labels[s:s + BLOCK]
        m = len(lab)
        shift = rng.integers(-2, 3, size=(m, 2))
        rows = (ar[None, :] - shift[:, :1]) % SIDE          # np.roll, axis 0
        cols = (ar[None, :] - shift[:, 1:]) % SIDE          # np.roll, axis 1
        img = templates[lab[:, None, None], rows[:, :, None],
                        cols[:, None, :]]
        img *= (0.8 + 0.4 * rng.random((m, 1, 1), np.float32))
        img += 0.35 * rng.standard_normal((m, SIDE, SIDE), np.float32)
        images[s:s + m, :, :, 0] = img
    return {"image": images, "label": labels}


def generate(seed: int, n_train: int, n_test: int, n_classes: int = 10):
    """-> (train, test), each {"image": (n, 28, 28, 1) f32, "label": (n,) i32}."""
    rng = np.random.default_rng([seed, 0x1A6E5])
    templates = _templates(rng, n_classes)
    return _draw(rng, templates, n_train), _draw(rng, templates, n_test)
