"""The numbers that decide ``correct`` for a cross-silo language-model
cell, leaf by leaf so that a billion weights never stand as one more
tree:

* ``loss``: the worst round of ``|L - L_ref| / |L_ref|`` of the round's
  loss (the mean over silos, as the round program reports it);
* ``update1`` / ``update1_diff``: the first round's global update
  ``d = p1 - p0`` against the reference's, per leaf by the gap of norms
  and by the norm of the difference, each over the larger of the
  reference leaf's norm and of the median leaf's; the worst leaf.
  Leaves whose reference update is under ``compare.STILL`` of the
  median leaf's are left out (rounding alone moves them);
* ``body_update_diff``: the norm of the difference over the reference's
  own norm, for the feature extractor's ``BODY`` slices (layer 0's q
  and MLP up projections). Their steps sit below a bfloat16 weight's
  half-ulp, so a bfloat16 carry loses them first; against their own
  norm the loss shows where the median leaf's would hide it;
* ``change2``: the gap of norms of the whole change after two rounds.

The norms are taken one leaf at a time on a device (``leaf_norms``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.compare import STILL

#: (path in the weights, row of the layer stack) of the feature
#: extractor's compared slices: layer 0's q and MLP up projections
BODY = ((("body", "attn", "wq", "w"), 0), (("body", "mlp", "w_in", "w"), 0))


@jax.jit
def _three_norms(x0, xa, xb):
    """(||xa - x0||, ||xb - x0||, ||xa - xb||) of one leaf, on a device.
    In f32 the differences of two close weights are exact; the sums of
    squares carry a relative error far under the compared gaps."""
    x0, xa, xb = (jnp.asarray(x, jnp.float32) for x in (x0, xa, xb))
    ua, ub = xa - x0, xb - x0

    def norm(v):
        return jnp.sqrt(jnp.sum(jnp.square(v)))
    return jnp.stack([norm(ua), norm(ub), norm(ua - ub)])


def _at(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def leaf_norms(p0, a, b) -> list[tuple[float, float, float]]:
    """Per leaf: (||a - p0||, ||b - p0||, ||a - b||), one leaf at a time
    on the first device."""
    return [tuple(float(v) for v in np.asarray(_three_norms(x0, xa, xb)))
            for x0, xa, xb in zip(jax.tree.leaves(p0), jax.tree.leaves(a),
                                  jax.tree.leaves(b))]


def _gaps(stats, keep, med) -> tuple[float, float]:
    """(worst gap of norms, worst norm of the difference)."""
    g = [(abs(a - b) / max(b, med), d / max(b, med))
         for (a, b, d), k in zip(stats, keep) if k]
    if not np.all(np.isfinite(g)):
        return float("inf"), float("inf")
    return max(x for x, _ in g), max(y for _, y in g)


def body_diff(p0, ref_p, got_p) -> float:
    worst = 0.0
    for path, row in BODY:
        _, n_ref, d = (float(v) for v in np.asarray(_three_norms(
            *(np.asarray(_at(t, path)[row]) for t in (p0, got_p, ref_p)))))
        gap = d / n_ref
        worst = max(worst, gap if np.isfinite(gap) else float("inf"))
    return worst


def numbers(p0, ref: dict, got: dict) -> dict:
    """``ref``/``got``: {"loss": [per round], "p1", "p2": weights after
    rounds one and two}; ``p0`` the weights both started from."""
    s1 = leaf_norms(p0, got["p1"], ref["p1"])
    n_ref = [b for _, b, _ in s1]
    med_all = float(np.median(n_ref))
    keep = [b >= STILL * med_all for b in n_ref]
    med = float(np.median([b for b, k in zip(n_ref, keep) if k]))
    update1, update1_diff = _gaps(s1, keep, med)
    change2, _ = _gaps(leaf_norms(p0, got["p2"], ref["p2"]), keep, med)
    lv, lr = np.asarray(got["loss"], np.float64), np.asarray(ref["loss"])
    loss = (float(np.max(np.abs(lv - lr) / np.abs(lr)))
            if np.all(np.isfinite(lv)) else float("inf"))
    return {"loss": loss, "update1": update1, "update1_diff": update1_diff,
            "body_update_diff": body_diff(p0, ref["p1"], got["p1"]),
            "change2": change2}


def leaf_report(p0, ref: dict, got: dict) -> dict:
    """Per leaf, for a look by hand: the reference's first update norm
    and the program's gaps against it."""
    s1 = leaf_norms(p0, got["p1"], ref["p1"])
    med = float(np.median([b for _, b, _ in s1]))
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(p0)]
    return {p: {"ref_norm": b, "update1": abs(a - b) / max(b, med),
                "update1_diff": d / max(b, med)}
            for p, (a, b, d) in zip(paths, s1)}
