"""The seeded generators: the same seed gives the same data, any seed
size is accepted, and the weights follow the reference's specs."""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.gen import images, weights  # noqa: E402
from chipbench.reference import cnn  # noqa: E402

CFG = {"sizes": {"image_shape": [28, 28, 1], "kernel": 5,
                 "conv_channels": [10, 20], "fc": [320, 120, 84, 10]},
       "dtype": "float32"}


def test_images_repeat_per_seed_and_differ_across_seeds():
    big = 2**31 + 12345
    a, _ = images.generate(big, 300, 50)
    b, _ = images.generate(big, 300, 50)
    c, _ = images.generate(big + 1, 300, 50)
    assert a["image"].shape == (300, 28, 28, 1)
    assert a["image"].dtype == np.float32 and a["label"].dtype == np.int32
    np.testing.assert_array_equal(a["image"], b["image"])
    assert not np.array_equal(a["image"], c["image"])
    assert set(np.unique(a["label"])) == set(range(10))


def test_images_blocks_match_one_draw_per_class_template():
    train, _ = images.generate(7, images.BLOCK + 20, 10)
    lab = train["label"]
    # images of one class correlate with each other far more than with
    # another class: the class template survives shift and noise
    x = train["image"].reshape(len(lab), -1)
    m0 = x[lab == 0].mean(0)
    m1 = x[lab == 1].mean(0)
    assert np.corrcoef(m0, x[lab == 0][:50].mean(0))[0, 1] > 0.8
    assert abs(np.corrcoef(m0, m1)[0, 1]) < 0.9


def test_every_seed_gives_the_same_work():
    from chipbench.reference import schedule
    sizes = set()
    for seed in (3, 2**31 + 11, 4_000_000_037):
        train, test = images.generate(seed, 6000, 1000)
        assert np.all(np.bincount(train["label"]) == 600)
        assert np.all(np.bincount(test["label"]) == 100)
        parts = schedule.shards(train["label"], 50, seed % 2**31)
        sizes.add(tuple(sorted(len(p) for p in parts)))
    assert sizes == {(120,) * 50}


def test_weights_follow_specs_and_seed():
    specs = cnn.param_specs(CFG)
    w1 = weights.make(specs, 2**31 + 3)
    w2 = weights.make(specs, 2**31 + 3)
    w3 = weights.make(specs, 4)
    assert w1["fc1"]["w"].shape == (320, 120)
    assert np.all(np.asarray(w1["fc3"]["b"]) == 0)
    assert float(np.abs(np.asarray(w1["fc1"]["w"])).max()) <= 320 ** -0.5
    np.testing.assert_array_equal(np.asarray(w1["body"]["conv1"]["w"]),
                                  np.asarray(w2["body"]["conv1"]["w"]))
    assert not np.array_equal(np.asarray(w1["body"]["conv1"]["w"]),
                              np.asarray(w3["body"]["conv1"]["w"]))
