"""The fused server-plane kernel suite (repro.kernels.server_plane).

Three layers of nets:
  * kernel-body parity — every server-plane Pallas kernel against its
    jnp oracle in interpret mode on CPU: f32 AND bf16 inputs,
    non-multiple-of-block N (the padding path), K=1 edge case.
    Tolerances are 1-2 ulp: the op sequence is shared, only XLA's
    shape-dependent FMA contraction differs.
  * strategy routing — all five registered strategies produce the same
    update through every ``fl.server_plane`` impl ("fused" == "ref"
    bit-identical off-TPU; "interpret" allclose), and the sync plane
    over a real model's multi-dtype parameter tree is the plain
    per-leaf rule (tests/plain_server.py).
  * engine — the fused plane inside the real chunked-scan engine
    matches the per-round loop bit-identically (the main nets live in
    tests/test_engine.py; here the interpret-mode kernel rides the scan
    to prove the Pallas body composes with lax.scan + donation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import strategies
from repro.kernels import ref
from repro.kernels.server_plane import (server_adam_flat, server_async_flat,
                                        server_mix_flat)
from repro.models.api import CLASSIFIER_KEYS
from plain_server import SCHEDULES, PlainServer, schedule

TOL = {jnp.float32: dict(rtol=2e-6, atol=2e-6),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _close(got, want, dtype):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **TOL[dtype])


def _flat_world(rng, K, N, dtype, Q=5):
    return dict(
        prev=jnp.asarray(rng.randn(N), dtype),
        stacked=jnp.asarray(rng.randn(K, N), dtype),
        sizes=jnp.asarray(rng.rand(K) + 0.5, jnp.float32),
        keep=jnp.asarray((rng.rand(K) < 0.7).astype(np.float32)),
        coefs=jnp.asarray([0.1, 2.5e-3, 0.95, 7.0], jnp.float32),
        qsum=jnp.asarray(rng.randn(Q, N).astype(np.float32)),
        qgamma=jnp.asarray(rng.rand(Q), jnp.float32),
        delays=jnp.asarray(rng.randint(1, Q, K), jnp.int32),
        tq=jnp.asarray([7, 7 % Q], jnp.int32),
        hyp=jnp.asarray([0.1, 2.5e-3, 0.95, 0.6], jnp.float32),
        m=jnp.asarray(rng.randn(N).astype(np.float32)),
        v=jnp.abs(jnp.asarray(rng.randn(N).astype(np.float32))),
        scalars=jnp.asarray([0.9, 0.99, 0.1, 1e-3, 3.0], jnp.float32),
    )


# --------------------------------------------- kernel-body parity nets ----

@pytest.mark.parametrize("N,block", [(4096, 1024), (4096 + 17, 1024),
                                     (100, 1024)])  # padding / block > N
@pytest.mark.parametrize("K", [1, 7])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_server_mix_kernel_matches_oracle(N, block, K, dtype):
    w = _flat_world(np.random.RandomState(N + K), K, N, dtype)
    got = server_mix_flat(w["prev"], w["stacked"], w["sizes"], w["keep"],
                          w["coefs"], block=block, interpret=True)
    want = ref.server_mix_math(w["prev"], w["stacked"], w["sizes"],
                               w["keep"], w["coefs"])
    assert got.dtype == w["prev"].dtype
    _close(got, want, dtype)


@pytest.mark.parametrize("N,block", [(2048, 512), (2048 + 31, 512)])
@pytest.mark.parametrize("K", [1, 6])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_server_async_kernel_matches_oracle(N, block, K, dtype):
    w = _flat_world(np.random.RandomState(N + K), K, N, dtype)
    delayed = (np.random.RandomState(K).rand(K) < 0.6).astype(np.float32)
    got = server_async_flat(w["prev"], w["stacked"], w["qsum"], w["qgamma"],
                            w["sizes"], jnp.asarray(delayed), w["delays"],
                            w["tq"], w["hyp"], block=block, interpret=True)
    want = ref.server_async_math(w["prev"], w["stacked"], w["qsum"],
                                 w["qgamma"], w["sizes"],
                                 jnp.asarray(delayed), w["delays"],
                                 w["tq"], w["hyp"])
    assert got[0].dtype == w["prev"].dtype
    assert got[1].dtype == jnp.float32 and got[2].dtype == jnp.float32
    _close(got, want, dtype)


@pytest.mark.parametrize("N,block", [(2048, 512), (2048 + 31, 512)])
@pytest.mark.parametrize("K", [1, 6])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_server_adam_kernel_matches_oracle(N, block, K, dtype):
    w = _flat_world(np.random.RandomState(N + K), K, N, dtype)
    got = server_adam_flat(w["prev"], w["stacked"], w["m"], w["v"],
                           w["sizes"], w["keep"], w["scalars"],
                           block=block, interpret=True)
    want = ref.server_adam_math(w["prev"], w["stacked"], w["m"], w["v"],
                                w["sizes"], w["keep"], w["scalars"])
    assert got[0].dtype == w["prev"].dtype
    _close(got, want, dtype)


def test_mix_empty_round_falls_back_to_prev():
    """keep == 0 for everyone: the whole beta budget reverts to the
    previous model (no NaNs from the 0/0 weight normalisation)."""
    w = _flat_world(np.random.RandomState(0), 4, 1024, jnp.float32)
    keep = jnp.zeros(4, jnp.float32)
    got = server_mix_flat(w["prev"], w["stacked"], w["sizes"], keep,
                          w["coefs"], block=512, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(w["prev"]))


# ------------------------------------------------- strategy routing nets ----

def _tree(rng, C=None):
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    return ({"a": f(3, 4), "b": {"c": f(5)}} if C is None
            else {"a": f(C, 3, 4), "b": {"c": f(C, 5)}})


def _sched(rng, C, max_delay=0):
    delayed = rng.rand(C) < 0.4
    delays = np.where(delayed, rng.randint(1, max(max_delay, 1) + 1, C), 1)
    return {"limited": jnp.asarray(rng.rand(C) < 0.5),
            "delayed": jnp.asarray(delayed),
            "delays": jnp.asarray(delays.astype(np.int32)),
            "data_sizes": jnp.asarray(rng.rand(C) + 0.5, jnp.float32)}


@pytest.mark.parametrize("algo,md", [("ama", 0), ("ama_fes", 3),
                                     ("fedavg", 0), ("fedprox", 0),
                                     ("fedopt", 0)])
def test_every_strategy_consistent_across_impls(algo, md):
    """fused == ref bit-identically off-TPU (same dispatch); interpret
    (the real Pallas body) allclose — params AND aux state (ring buffer,
    moments)."""
    rng = np.random.RandomState(42)
    base = dict(algorithm=algo, max_delay=md, p_delay=0.4 if md else 0.0)
    prev, cp = _tree(rng), _tree(rng, C=4)
    sched = _sched(rng, 4, max_delay=md)
    outs = {}
    for impl in ("fused", "ref", "interpret"):
        s = strategies.resolve(FLConfig(server_plane=impl, **base))
        outs[impl] = s.fused_server_update(2, prev, cp, sched,
                                           s.init_state(prev))
    for g, w in zip(jax.tree.leaves(outs["fused"]),
                    jax.tree.leaves(outs["ref"])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip(jax.tree.leaves(outs["fused"]),
                    jax.tree.leaves(outs["interpret"])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", SCHEDULES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_server_mix_tree_over_the_paper_cnn(dtype, kind):
    """The sync plane over the paper CNN's own parameter tree: with the
    body in bf16 and the classifier in f32 the tree is two dtype groups,
    each flattened to one kernel call (interpret mode) and split back
    into its leaves; the result is the plain per-leaf AMA update."""
    from repro.configs.registry import ARCHS
    from repro.models.api import build_model
    rng = np.random.RandomState(13)
    params = build_model(ARCHS["paper-cnn"]).init(jax.random.PRNGKey(0))
    prev = {k: jax.tree.map(lambda x: x.astype(
        dtype if k not in CLASSIFIER_KEYS else jnp.float32), v)
        for k, v in params.items()}
    cp = jax.tree.map(lambda p: (p.astype(jnp.float32) + jnp.asarray(
        rng.randn(4, *p.shape) * 0.1, jnp.float32)).astype(p.dtype), prev)
    sched = schedule(rng, 4, kind)
    fl = FLConfig(algorithm="ama", alpha0=0.2, eta=0.05,
                  server_plane="interpret")
    got, _ = strategies.resolve(fl).fused_server_update(3, prev, cp, sched,
                                                        {})
    want = PlainServer(fl).step(3, prev, cp, sched)
    for g, w, p in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(prev)):
        assert g.dtype == p.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32), w,
                                   **TOL[p.dtype.type])


# ------------------------------------------------------- engine net ----

def test_interpret_kernel_rides_scan_and_matches_loop():
    """The Pallas kernel body (interpret mode) composes with the fused
    lax.scan engine: scan == per-round loop bit-identically, and the
    result matches the default fused dispatch to tight tolerance."""
    from repro.configs.registry import ARCHS
    from repro.core.simulation import FederatedSimulation
    from repro.data.partition import shard_partition
    from repro.data.pipeline import build_clients
    from repro.data.synth import make_image_classification
    from repro.models.api import build_model

    train, test = make_image_classification(n_train=160, n_test=40, seed=0)
    clients = build_clients(train, shard_partition(train["label"], 6,
                                                   seed=0))
    model = build_model(ARCHS["paper-cnn"])
    states = {}
    for impl, use_scan in [("interpret", True), ("interpret", False),
                           ("fused", True)]:
        fl = FLConfig(num_clients=6, clients_per_round=3, local_epochs=1,
                      local_batch_size=8, lr=0.1, algorithm="ama_fes",
                      max_delay=2, p_delay=0.4, seed=0,
                      server_plane=impl)
        sim = FederatedSimulation(model, fl, clients, test,
                                  use_scan=use_scan)
        sim.run(rounds=2, eval_every=2)
        states[(impl, use_scan)] = sim.state
    for g, w in zip(jax.tree.leaves(states[("interpret", True)]),
                    jax.tree.leaves(states[("interpret", False)])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip(jax.tree.leaves(states[("interpret", True)]),
                    jax.tree.leaves(states[("fused", True)])):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-4, atol=1e-5)
