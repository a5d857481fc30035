"""The numbers that decide ``correct`` for a training cell.

Each is a relative gap between what the program produced and what the
plain reference produced from the same seed:

* ``loss``: the worst round of ``|L - L_ref| / |L_ref|``;
* ``update1_diff``: per leaf, the norm of the difference between the
  program's first-round update and the reference's, over the larger of
  the reference's norm of that leaf and of the median leaf; the worst
  leaf. The gap of norms cannot see a fault that leaves an update's
  size and changes its direction, such as training on half of each
  batch; this number can;
* ``out_bias_diff``: the same measure for the output layer's bias
  alone. Its update is the batch mean of ``softmax - onehot``, which
  rounding of the products barely moves and which the samples of each
  batch set: on the chip it separates training on half of each batch
  from sound runs where the worst leaf of ``update1_diff`` does not
  (PERF.md);
* a norm gap: per leaf, ``| ||d|| - ||d_ref|| |`` over the larger of the
  reference's norm of that leaf and of the median leaf, and the worst
  leaf. ``d`` is a change of the weights (after one round: the update the
  server applied; after three: the whole change). Leaves whose first
  reference update is under a thousandth of the median leaf's are left
  out: they move by rounding alone.
"""
from __future__ import annotations

import jax
import numpy as np

#: a leaf moves by rounding alone below this share of the median leaf
STILL = 1e-3


def leaf_norms(tree) -> list[float]:
    return [float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for x in jax.tree.leaves(tree)]


def diff(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def moving_leaves(first_update_ref) -> list[bool]:
    n = leaf_norms(first_update_ref)
    med = float(np.median(n))
    return [v >= STILL * med for v in n]


def norm_gap(d, d_ref, keep: list[bool]) -> float:
    n, n_ref = leaf_norms(d), leaf_norms(d_ref)
    med = float(np.median([v for v, k in zip(n_ref, keep) if k]))
    gaps = [abs(a - b) / max(b, med)
            for a, b, k in zip(n, n_ref, keep) if k]
    return float(max(gaps)) if np.all(np.isfinite(gaps)) else float("inf")


def diff_norm(d, d_ref, keep: list[bool]) -> float:
    n_ref = leaf_norms(d_ref)
    med = float(np.median([v for v, k in zip(n_ref, keep) if k]))
    gaps = [a / max(b, med) for a, b, k in
            zip(leaf_norms(diff(d, d_ref)), n_ref, keep) if k]
    return float(max(gaps)) if np.all(np.isfinite(gaps)) else float("inf")


def leaf_report(p0, ref, got) -> dict:
    """Per leaf, for a look by hand: the reference's first update norm
    and the program's update1 and update1_diff gaps against it."""
    u_ref, u = diff(ref["p1"], p0), diff(got["p1"], p0)
    n_ref = leaf_norms(u_ref)
    med = float(np.median(n_ref))
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(u_ref)]
    return {p: {"ref_norm": b,
                "update1": abs(a - b) / max(b, med),
                "update1_diff": c / max(b, med)}
            for p, a, b, c in zip(paths, leaf_norms(u), n_ref,
                                  leaf_norms(diff(u, u_ref)))}


def rel_gap(values, ref_values) -> float:
    v, r = np.asarray(values, np.float64), np.asarray(ref_values, np.float64)
    if not np.all(np.isfinite(v)):
        return float("inf")
    return float(np.max(np.abs(v - r) / np.abs(r)))


def leaf_at(tree, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


def one_leaf_diff(d, d_ref, keep: list[bool], path: tuple) -> float:
    med = float(np.median([v for v, k in zip(leaf_norms(d_ref), keep) if k]))
    a, b = leaf_at(d, path), leaf_at(d_ref, path)
    gap = float(np.linalg.norm(np.ravel(a - b)))
    return (gap / max(float(np.linalg.norm(np.ravel(b))), med)
            if np.isfinite(gap) else float("inf"))


def training_numbers(p0, ref, got, out_bias: tuple) -> dict:
    """``ref``/``got``: {"loss": [per round], "p1": weights after round
    one, "p3": after round three, "eval_loss": [per round]};
    ``out_bias``: the path of the output layer's bias in the weights."""
    u_ref, u = diff(ref["p1"], p0), diff(got["p1"], p0)
    keep = moving_leaves(u_ref)
    return {
        "loss": rel_gap(got["loss"], ref["loss"]),
        "update1": norm_gap(u, u_ref, keep),
        "update1_diff": diff_norm(u, u_ref, keep),
        "out_bias_diff": one_leaf_diff(u, u_ref, keep, out_bias),
        "change3": norm_gap(diff(got["p3"], p0), diff(ref["p3"], p0), keep),
        "eval_loss": rel_gap(got["eval_loss"], ref["eval_loss"]),
    }
