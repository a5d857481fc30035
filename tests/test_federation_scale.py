"""Million-client federation machinery: hashed virtual populations,
O(cohort) selection, streamed shard staging, and the pre-reduced client
axis.

The safety nets for the scale PR:
  * the dense draw sequence is UNTOUCHED below the guards (rng.choice
    up to DENSE_SELECT_MAX clients, dense arrays up to VIRTUAL_K_MIN) —
    the seed's bit-identity contract survives;
  * the virtual path honours the SAME contracts as the dense one
    (batch row i == round(t0+i), purity in t, fresh-instance agreement)
    at K = 10^5;
  * VirtualClientShards stages bit-identical batches to a dense
    ClientDataset list built from the same shard views — so the whole
    engine run (5 strategies x scan / per-round loop) is bit-identical
    streamed vs dense;
  * reduced_server_update (the sharded-client-axis path) matches the
    fused server plane for every registered strategy, params AND aux.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import env as env_mod
from repro.configs.base import FLConfig
from repro.configs.registry import ARCHS
from repro.core import strategies
from repro.core.simulation import FederatedSimulation
from repro.data.pipeline import (ClientDataset, VirtualClientShards,
                                 stage_round_indices)
from repro.data.synth import make_image_classification
from repro.env.base import UniformParticipation
from repro.env.virtual import (DENSE_SELECT_MAX, VIRTUAL_K_MIN,
                               floyd_sample, is_virtual,
                               select_batch_hashed)
from repro.models.api import build_model

CANONICAL = sorted({cls.name for cls in map(env_mod.get, env_mod.names())})
#: environments with a K-free realisation (trace replay stays dense)
VIRT_ENVS = [n for n in CANONICAL if env_mod.get(n).supports_virtual]

STRATS = [("ama", 0), ("async_ama", 3), ("fedavg", 0), ("fedprox", 0),
          ("fedopt", 0)]


@pytest.fixture(scope="module")
def small_world():
    train, test = make_image_classification(n_train=240, n_test=60, seed=0)
    model = build_model(ARCHS["paper-cnn"])
    return model, train, test


def _fl(**kw):
    base = dict(num_clients=20, clients_per_round=5, local_epochs=1,
                local_batch_size=10, lr=0.1, p_limited=0.25, seed=0)
    base.update(kw)
    return FLConfig(**base)


def assert_states_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------ O(m) selection ----------

def test_dense_select_guard_is_bit_identical():
    """Below DENSE_SELECT_MAX the draw must stay EXACTLY rng.choice —
    any change breaks every committed seed at paper scale."""
    fl = _fl(num_clients=256, clients_per_round=7)
    got = UniformParticipation(fl).select(0, np.random.RandomState(7))
    want = np.random.RandomState(7).choice(
        256, size=7, replace=False).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_floyd_sample_valid_and_deterministic():
    K, m = 1_000_000, 257
    a = floyd_sample(np.random.RandomState(11), K, m)
    b = floyd_sample(np.random.RandomState(11), K, m)
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.int32 and a.shape == (m,)
    assert len(np.unique(a)) == m
    assert a.min() >= 0 and a.max() < K
    # O(m), not O(K): the rng consumed m draws, not a K permutation
    assert DENSE_SELECT_MAX < K


def test_select_batch_hashed_contract():
    fl = _fl(num_clients=1_000_000, clients_per_round=128,
             population="virtual")
    sel = select_batch_hashed(fl, 5, 16)
    assert sel.shape == (16, 128) and sel.dtype == np.int32
    assert sel.min() >= 0 and sel.max() < 1_000_000
    for row in sel:                       # without replacement per round
        assert len(np.unique(row)) == 128
    # pure in t: any chunking yields the same rows
    np.testing.assert_array_equal(select_batch_hashed(fl, 8, 1)[0], sel[3])
    np.testing.assert_array_equal(select_batch_hashed(fl, 5, 4), sel[:4])


def test_is_virtual_guard():
    assert not is_virtual(_fl())                       # auto, tiny K
    assert is_virtual(_fl(num_clients=VIRTUAL_K_MIN + 1))
    assert not is_virtual(_fl(num_clients=VIRTUAL_K_MIN + 1,
                              population="dense"))
    assert is_virtual(_fl(population="virtual"))
    with pytest.raises(ValueError):
        is_virtual(_fl(population="bogus"))


# ------------------------------------------ virtual environment layer -----

@pytest.mark.parametrize("name", VIRT_ENVS)
def test_virtual_batch_rows_bit_identical_to_rounds(name):
    """THE schedule contract, at K = 10^5 where the dense path would
    materialise (K,) state: batch row i == round(t0 + i), and a fresh
    instance queried out of order agrees."""
    fl = _fl(num_clients=100_000, clients_per_round=8, env=name,
             p_delay=0.4, max_delay=6)
    e = env_mod.get(name)(fl)
    assert e.virtual
    got = e.batch(3, 5)
    assert got["selected"].shape == (5, 8)
    for i in range(5):
        rs = e.round(3 + i)
        np.testing.assert_array_equal(got["selected"][i], rs.selected)
        np.testing.assert_array_equal(got["limited"][i], rs.limited)
        np.testing.assert_array_equal(got["delayed"][i], rs.delayed)
        np.testing.assert_array_equal(got["delays"][i], rs.delays)
        np.testing.assert_array_equal(got["data_sizes"][i], rs.data_sizes)
    fresh = env_mod.get(name)(fl)
    rs = fresh.round(7)                   # first query, deep into the run
    np.testing.assert_array_equal(got["delays"][4], rs.delays)


def test_trace_env_never_virtual():
    """Trace replay is a recording of a CONCRETE population — it must
    refuse the virtual realisation even when the guard would fire."""
    assert env_mod.get("trace").supports_virtual is False
    e = env_mod.get("trace")(_fl(env="trace", population="virtual"))
    assert not e.virtual


# ------------------------------------------------ streamed staging --------

def test_shard_views_are_pure_and_overlapping(small_world):
    _, train, _ = small_world
    K = 1000                              # K x shard_size >> n: wraps
    a = VirtualClientShards(train, K, shard_size=24, seed=3)
    b = VirtualClientShards(train, K, shard_size=24, seed=3)
    assert len(a) == K and a.min_size == 24
    np.testing.assert_array_equal(a.shard_indices(917), b.shard_indices(917))
    assert not np.array_equal(a.shard_indices(0), a.shard_indices(1))
    for i in (0, 1, 999):
        idx = a.shard_indices(i)
        assert idx.shape == (24,) and idx.min() >= 0 and idx.max() < 240
    sizes = a.client_sizes(np.array([[3, 917], [5, 0]]))
    np.testing.assert_array_equal(sizes, np.full((2, 2), 24, np.float32))


def test_streamed_staging_matches_dense_list(small_world):
    """VirtualClientShards and a dense ClientDataset list built from the
    SAME shard views consume the shared per-round stream identically."""
    _, train, _ = small_world
    shards = VirtualClientShards(train, 20, shard_size=24, seed=0)
    dense = [ClientDataset(train, shards.shard_indices(i))
             for i in range(20)]
    sel = np.array([3, 19, 0, 7, 11])
    for t in (0, 9):
        np.testing.assert_array_equal(
            stage_round_indices(shards, sel, 0, t, steps=2, batch_size=10),
            stage_round_indices(dense, sel, 0, t, steps=2, batch_size=10))


@pytest.mark.parametrize("use_scan", [True, False])
@pytest.mark.parametrize("algo,md", STRATS)
def test_streamed_engine_bit_identical_to_dense(small_world, algo, md,
                                                use_scan):
    """The whole engine run — every strategy, fused scan AND per-round
    loop — is bit-identical streamed (VirtualClientShards) vs dense
    (ClientDataset list over the same shard views)."""
    model, train, test = small_world
    fl = _fl(algorithm=algo, env="bernoulli", max_delay=md,
             p_delay=0.4 if md else 0.0)
    shards = VirtualClientShards(train, 20, shard_size=24, seed=0)
    dense = [ClientDataset(train, shards.shard_indices(i))
             for i in range(20)]
    sims = {k: FederatedSimulation(model, fl, c, test, use_scan=use_scan)
            for k, c in (("streamed", shards), ("dense", dense))}
    hists = {k: s.run(rounds=4, eval_every=2) for k, s in sims.items()}
    assert_states_identical(sims["streamed"].state, sims["dense"].state)
    assert hists["streamed"].train_loss == hists["dense"].train_loss
    assert hists["streamed"].test_acc == hists["dense"].test_acc


def test_prefetch_depth_is_plumbed_and_bit_identical(small_world):
    model, train, test = small_world
    shards = VirtualClientShards(train, 20, shard_size=24, seed=0)
    runs = {}
    for depth in (1, 3):
        fl = _fl(env="bernoulli", p_delay=0.3, max_delay=4,
                 prefetch_depth=depth)
        assert fl.prefetch_depth == depth
        sim = FederatedSimulation(model, fl, shards, test)
        sim.run(rounds=4, eval_every=2)
        runs[depth] = sim.state
    assert_states_identical(runs[1], runs[3])


# -------------------------------- pre-reduced client axis (sharded) -------

@pytest.mark.parametrize("algo,md", STRATS)
def test_reduced_server_update_matches_fused(algo, md):
    """reduced_server_update — the weighted client-axis contraction that
    runs BEFORE the server plane when the mesh shards "client" — must
    match the fused plane on params AND aux (async ring buffer, fedopt
    moments) for every registered strategy."""
    fl = _fl(algorithm=algo, max_delay=md, p_delay=0.4 if md else 0.0)
    strategy = strategies.get(algo)(fl)
    rng = np.random.RandomState(0)
    C = fl.clients_per_round
    params = {"w": jnp.asarray(rng.randn(6, 4), jnp.float32),
              "b": jnp.asarray(rng.randn(4), jnp.float32)}
    client_params = jax.tree.map(
        lambda p: p + jnp.asarray(rng.randn(C, *p.shape) * 0.1,
                                  jnp.float32), params)
    delayed = jnp.asarray(rng.rand(C) < 0.4) if md else jnp.zeros(C, bool)
    sched = {"data_sizes": jnp.asarray(rng.randint(5, 40, C), jnp.float32),
             "delayed": delayed,
             "delays": jnp.where(delayed, 1 + jnp.asarray(
                 rng.randint(0, max(md, 1), C)), 1).astype(jnp.int32),
             "limited": jnp.zeros(C, bool)}
    aux = strategy.init_state(params)
    t = jnp.asarray(3, jnp.int32)
    fused_p, fused_aux = strategy.fused_server_update(
        t, params, client_params, sched, aux)
    red_p, red_aux = strategy.reduced_server_update(
        t, params, client_params, sched, aux)
    for a, b in zip(jax.tree.leaves(fused_p), jax.tree.leaves(red_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(fused_aux), jax.tree.leaves(red_aux)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_client_reduce_force_runs_end_to_end(small_world):
    """fl.client_reduce='force' routes every round through the reduced
    path on a 1-device mesh — the CPU equivalence configuration — and
    stays close to the fused default over a short run."""
    model, train, test = small_world
    shards = VirtualClientShards(train, 20, shard_size=24, seed=0)
    states = {}
    for mode in ("off", "force"):
        fl = _fl(env="bernoulli", p_delay=0.3, max_delay=4,
                 algorithm="async_ama", client_reduce=mode)
        sim = FederatedSimulation(model, fl, shards, test)
        sim.run(rounds=3, eval_every=3)
        states[mode] = sim.state
    for a, b in zip(jax.tree.leaves(states["off"]),
                    jax.tree.leaves(states["force"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-4, atol=1e-5)


def test_client_reduce_rejects_unknown_mode(small_world):
    model, train, test = small_world
    shards = VirtualClientShards(train, 20, shard_size=24, seed=0)
    fl = _fl(env="bernoulli", client_reduce="bogus")
    sim = FederatedSimulation(model, fl, shards, test)
    with pytest.raises(ValueError, match="client_reduce"):
        sim.run(rounds=1, eval_every=1)
