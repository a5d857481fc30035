"""Required FLOPs and bytes against hand counts."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import flops  # noqa: E402
from chipbench.run import load_json  # noqa: E402

CNN = load_json(ROOT / "chipbench" / "configs" / "paper-cnn.json")


def test_paper_cnn_forward_per_sample():
    body, clf = flops.cnn_forward(CNN)
    # conv1: 24*24*10 outputs x 25 MACs; conv2: 8*8*20 x 250 MACs
    assert body == 2 * (24 * 24 * 10 * 25 + 8 * 8 * 20 * 250)
    assert clf == 2 * (320 * 120 + 120 * 84 + 84 * 10)
    assert body + clf == pytest.approx(1.03e6, rel=0.01)


def test_cnn_round_counts_fes_and_eval():
    body, clf = flops.cnn_forward(CNN)
    f = body + clf
    full = flops.cnn_round(CNN, steps=2, batch=3, limited=[False],
                           n_eval=0)
    lim = flops.cnn_round(CNN, steps=2, batch=3, limited=[True], n_eval=0)
    ev = flops.cnn_round(CNN, steps=1, batch=1, limited=[], n_eval=5)
    assert full == 6 * 3 * f
    assert lim == 6 * (f + 2 * clf)
    assert ev == 5 * f
