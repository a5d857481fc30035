"""The plain reference agrees with the program's round at a tiny size,
and the run's result line has the contract's shape."""
import math
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import chipbench_tiny  # noqa: E402

from chipbench.reference import federated  # noqa: E402


def test_sound_run_is_correct_and_well_formed():
    out = chipbench_tiny.measure(chipbench_tiny.paper_context(5))
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    for k, c in out["checks"].items():
        # f32 on the CPU: the program and the reference agree to rounding;
        # the rounding of each step turns the update's direction more
        # than its size
        assert c["value"] < (1e-3 if k == "update1_diff" else 1e-4), (k, c)
    m = out["metrics"]
    assert set(m) == {"setup_s", "rounds_per_s", "round_ms_p90"} or \
        set(m) == {"setup_s", "rounds_per_s"}
    assert all(math.isfinite(v["value"]) and v["value"] > 0
               for v in m.values())
    assert out["device"]["count"] == 1


def test_ama_mix_is_eq5():
    prev = {"w": jnp.array([1.0, 2.0])}
    clients = [{"w": jnp.array([3.0, 3.0])}, {"w": jnp.array([0.0, 6.0])}]
    fl = {"alpha0": 0.1, "eta": 0.0025, "alpha_cap": 0.95}
    out = federated.ama_mix(prev, clients, np.array([1.0, 3.0]), 4, fl)
    a = 0.1 + 0.0025 * 4
    agg = 0.25 * np.array([3.0, 3.0]) + 0.75 * np.array([0.0, 6.0])
    np.testing.assert_allclose(np.asarray(out["w"]),
                               a * np.array([1.0, 2.0]) + (1 - a) * agg,
                               rtol=1e-6)


def test_limited_client_keeps_its_feature_extractor():
    from chipbench.gen import images, weights
    from chipbench.reference import cnn
    cfg = chipbench_tiny.paper_context().config
    p0 = weights.make(cnn.param_specs(cfg), 3)
    train, _ = images.generate(3, 70, 10)
    x = jnp.asarray(train["image"][:64].reshape(2, 32, 28, 28, 1))
    y = jnp.asarray(train["label"][:64].reshape(2, 32))
    p_lim, _ = federated.local_sgd(p0, x, y, True, lr=0.1,
                                   dtype=jnp.float32)
    p_full, _ = federated.local_sgd(p0, x, y, False, lr=0.1,
                                    dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(p_lim["body"]["conv1"]["w"]),
                                  np.asarray(p0["body"]["conv1"]["w"]))
    assert not np.array_equal(np.asarray(p_full["body"]["conv1"]["w"]),
                              np.asarray(p0["body"]["conv1"]["w"]))
    assert not np.array_equal(np.asarray(p_lim["fc1"]["w"]),
                              np.asarray(p0["fc1"]["w"]))
