"""The unified chunked-scan execution engine (repro.exec).

The safety net for the PR-3 refactor: the fused chunked-scan simulation
must be BIT-IDENTICAL to the per-round-jit fallback (per strategy x per
environment), staging must be pure in the round index (chunking/resume
invariant), the jitted batched eval exact, the full-round-state
checkpoint a bit-identical continuation, and the FL mesh a no-op at
CPU scale.
"""
import jax
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.configs.registry import ARCHS
from repro.core.simulation import FederatedSimulation
from repro.data.partition import shard_partition
from repro.data import pipeline
from repro.data.pipeline import (ChunkPrefetcher, DeviceStore,
                                 VirtualClientShards, build_clients,
                                 gather_chunk, place_store, stage_chunk,
                                 stage_chunk_indices, stage_round_indices,
                                 staging_bytes, store_fits_device)
from repro.obs.timing import PhaseTimes
from repro.data.synth import make_image_classification
from repro.exec.evals import Evaluator
from repro.launch.mesh import engine_mesh
from repro.models.api import build_model


@pytest.fixture(scope="module")
def small_world():
    train, test = make_image_classification(n_train=240, n_test=60, seed=0)
    clients = build_clients(train, shard_partition(train["label"], 8, seed=0))
    model = build_model(ARCHS["paper-cnn"])
    return model, train, clients, test


def _fl(**kw):
    base = dict(num_clients=8, clients_per_round=4, local_epochs=1,
                local_batch_size=10, lr=0.1, p_limited=0.25, seed=0)
    base.update(kw)
    return FLConfig(**base)


def assert_states_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------- the equivalence net ----

@pytest.mark.parametrize("env", ["bernoulli", "gilbert_elliott"])
@pytest.mark.parametrize("algo,md", [("ama", 0), ("async_ama", 3),
                                     ("fedavg", 0), ("fedprox", 0),
                                     ("fedopt", 0)])
def test_chunked_scan_bit_identical_to_per_round_loop(small_world, env,
                                                      algo, md):
    """Every registered strategy x {bernoulli, gilbert_elliott}: the
    chunked-scan engine and the --no-scan per-round loop produce
    bit-identical params, aux state AND History."""
    model, _, clients, test = small_world
    fl = _fl(algorithm=algo, env=env, max_delay=md,
             p_delay=0.4 if md else 0.0)
    sims = {s: FederatedSimulation(model, fl, clients, test, use_scan=s)
            for s in (True, False)}
    hists = {s: sim.run(rounds=4, eval_every=2) for s, sim in sims.items()}
    assert_states_identical(sims[True].state, sims[False].state)
    assert hists[True].train_loss == hists[False].train_loss
    assert hists[True].test_acc == hists[False].test_acc
    assert hists[True].test_loss == hists[False].test_loss
    assert len(hists[True].train_loss) == 4
    assert len(hists[True].test_acc) == 2
    assert sims[True].t == 4


# ------------------------------------------------------- data plane ----

def test_stage_chunk_rows_match_per_round_staging(small_world):
    """stage_chunk(t0, n) row i == staging round t0+i alone, and the
    gather reproduces each client's own shard samples."""
    model, train, clients, test = small_world
    sel = np.array([[0, 3, 5], [7, 1, 2], [4, 6, 0], [2, 2, 1]])
    chunk = stage_chunk(train, clients, sel, seed=0, t0=5, steps=3,
                        batch_size=4)
    assert chunk["image"].shape == (4, 3, 3, 4, 28, 28, 1)
    for i in range(4):
        idx = stage_round_indices(clients, sel[i], 0, 5 + i, 3, 4)
        np.testing.assert_array_equal(chunk["image"][i],
                                      train["image"][idx])
        np.testing.assert_array_equal(chunk["label"][i],
                                      train["label"][idx])
        # every drawn index belongs to the client's own shard
        for c in range(3):
            assert set(idx[c].ravel()) <= set(clients[sel[i][c]].indices)


def test_stage_chunk_is_index_draw_then_gather(small_world):
    """stage_chunk is its index draw composed with its gather, with or
    without a timer, which books the gather alone."""
    model, train, clients, _ = small_world
    sel = np.array([[0, 3, 5], [7, 1, 2]])
    idx = stage_chunk_indices(clients, sel, 4, 9, 3, 4)
    assert idx.shape == (2, 3, 3, 4)
    timer = PhaseTimes()
    for chunk in (stage_chunk(train, clients, sel, 4, 9, 3, 4),
                  stage_chunk(train, clients, sel, 4, 9, 3, 4,
                              timer=timer)):
        for k, v in gather_chunk(train, idx).items():
            np.testing.assert_array_equal(chunk[k], v)
    assert list(timer.calls) == ["stage_gather"]


def test_staging_pure_in_t_chunking_invariant(small_world):
    """Staging is keyed on the absolute round index: any chunking of the
    same rounds yields bit-identical batches (the resume guarantee)."""
    model, train, clients, _ = small_world
    sel = np.arange(8).reshape(4, 2) % 8
    whole = stage_chunk(train, clients, sel, seed=3, t0=2, steps=2,
                        batch_size=5)
    parts = [stage_chunk(train, clients, sel[i:i + 1], seed=3, t0=2 + i,
                         steps=2, batch_size=5) for i in range(4)]
    for k in whole:
        np.testing.assert_array_equal(
            whole[k], np.concatenate([p[k] for p in parts]))


@pytest.mark.parametrize("population,meshed", [("dense", False),
                                               ("virtual", False),
                                               ("dense", True)])
def test_device_store_stages_as_the_host_gather(small_world, population,
                                                meshed):
    """The device gather returns the host gather's batches bit for bit,
    for a dense client list and for virtual shards over the same store,
    and books "stage_gather" and a call of the "stage_device" counter."""
    _, train, clients, _ = small_world
    if population == "virtual":
        clients = VirtualClientShards(train, 20, shard_size=24, seed=0)
    store = DeviceStore(train, engine_mesh(3) if meshed else None)
    sel = np.array([[0, 3, 5], [7, 1, 2]])
    timer = PhaseTimes()
    got = stage_chunk(store, clients, sel, 4, 9, 3, 4, timer=timer)
    want = stage_chunk(train, clients, sel, 4, 9, 3, 4)
    assert set(got) == set(want)
    for k in want:
        assert isinstance(got[k], jax.Array)
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])
    assert timer.calls == {"stage_gather": 1, "stage_device": 1}
    assert timer.seconds["stage_device"] == 0.0


def test_device_store_rejects_indices_outside_the_store(small_world):
    """The device gather is told its indices are in bounds, so it
    refuses what the host gather would refuse."""
    _, train, _, _ = small_world
    store = DeviceStore(train)
    n = len(train["label"])
    with pytest.raises(IndexError):
        train["label"][np.array([[0, n]])]
    with pytest.raises(IndexError, match="outside a store"):
        store.gather(np.array([[0, n]]))
    np.testing.assert_array_equal(
        np.asarray(store.gather(np.array([[-1, 2]]))["label"]),
        train["label"][np.array([[-1, 2]])])


@pytest.mark.parametrize("store_bytes,limit,fits", [
    (100, 400, True), (101, 400, False), (10**9, 16 * 10**9, True),
    (100, None, False)])
def test_store_placement_rule(store_bytes, limit, fits):
    """On the device while staging takes up to a quarter of its memory;
    on the host past it, or where the backend reports no memory (the
    CPU)."""
    assert store_fits_device(store_bytes, limit) is fits


@pytest.mark.parametrize("limit,on_device", [(None, False), (10**12, True),
                                             (1000, False)])
def test_place_store_follows_the_reported_memory(small_world, monkeypatch,
                                                 limit, on_device):
    _, train, _, _ = small_world
    _limit(monkeypatch, limit)
    store = place_store(train, 8, 3)
    assert isinstance(store, DeviceStore) is on_device
    if on_device:
        assert place_store(train, 8, 3, placed=store) is store
    else:
        assert store is train


def test_staging_bytes_counts_store_chunks_and_scratch(small_world):
    """The store and the gather's scratch in padded rows, the chunks in
    flight in the samples' own shape."""
    _, train, _, _ = small_world
    n, img = len(train["label"]), train["image"]
    row = img[0].size * img.itemsize + train["label"].itemsize
    padded = (-(-img[0].size // pipeline.ROW_ALIGN) * pipeline.ROW_ALIGN
              * img.itemsize + train["label"].itemsize)
    assert staging_bytes(train, 100, 3) == (n + 100) * padded + 300 * row


@pytest.mark.parametrize("samples,in_flight,on_device", [
    (1000, 3, True), (1001, 3, False), (1000, 4, False)])
def test_place_store_counts_the_chunks_in_flight(small_world, monkeypatch,
                                                 samples, in_flight,
                                                 on_device):
    """A store that fits alone still gathers on the host where the
    chunks staging holds beside it, and their scratch, would not fit."""
    _, train, _, _ = small_world
    _limit(monkeypatch, 4 * staging_bytes(train, 1000, 3))
    assert isinstance(place_store(train, samples, in_flight),
                      DeviceStore) is on_device


def _limit(monkeypatch, limit):
    """Every device reports ``limit`` bytes of memory (None: no memory
    stats, as the CPU)."""
    monkeypatch.setattr(pipeline, "device_bytes_limit", lambda devs: limit)


def _run_on(monkeypatch, device: bool, sim, **kwargs):
    """``sim.run`` with the sample store the placement rule puts on the
    device (``device``) or leaves on the host."""
    _limit(monkeypatch, 10**12 if device else None)
    hist = sim.run(**kwargs)
    assert isinstance(sim._store, DeviceStore) is device
    return hist


@pytest.mark.parametrize("use_scan", [True, False])
def test_device_store_engine_bit_identical_to_host_gather(small_world,
                                                          monkeypatch,
                                                          use_scan):
    """A run gathering from the device store and one gathering on the
    host give the same params, aux and History bit for bit, on the
    fused scan and the per-round path, over a chunk boundary."""
    model, _, clients, test = small_world
    fl = _fl(algorithm="async_ama", max_delay=3, p_delay=0.4)
    sims = {d: FederatedSimulation(model, fl, clients, test,
                                   use_scan=use_scan) for d in (True, False)}
    hists = {d: _run_on(monkeypatch, d, sim, rounds=3, eval_every=2)
             for d, sim in sims.items()}
    assert_states_identical(sims[True].state, sims[False].state)
    for k in ("train_loss", "test_acc", "test_loss", "eval_rounds"):
        assert getattr(hists[True], k) == getattr(hists[False], k)


@pytest.mark.parametrize("device", [True, False])
def test_stage_device_counts_device_chunks(small_world, monkeypatch, device):
    """"stage_device" is booked once per chunk gathered on the device,
    and never by the host gather."""
    model, _, clients, test = small_world
    sim = FederatedSimulation(model, _fl(), clients, test)
    _run_on(monkeypatch, device, sim, rounds=3, eval_every=2)
    calls = sim.timer.calls
    assert calls["stage"] == calls["stage_gather"] == 2
    assert calls.get("stage_device", 0) == (2 if device else 0)


@pytest.mark.parametrize("depth", [1, 3])
def test_engine_places_the_store_for_its_chunks(small_world, monkeypatch,
                                                depth):
    """Each run counts its own chunks in flight, prefetch_depth + 2 of
    them: on a device that holds the store and three one-round chunks,
    one-round chunks at depth 1 gather on the device; two-round chunks,
    or a deeper prefetch, take the host gather, and the device store is
    dropped."""
    model, train, clients, test = small_world
    sim = FederatedSimulation(model, _fl(prefetch_depth=depth), clients,
                              test)
    per_round = 4 * sim._steps_per_round() * 10
    _limit(monkeypatch, 4 * staging_bytes(train, per_round, 3))
    sim.run(rounds=2, eval_every=1)
    assert isinstance(sim._store, DeviceStore) is (depth == 1)
    sim.run(rounds=2, eval_every=2)
    assert sim._store is sim.data
    assert sim.timer.calls["stage"] == 3
    assert sim.timer.calls.get("stage_device", 0) == (2 if depth == 1
                                                      else 0)


def test_chunk_prefetcher_orders_and_propagates_errors():
    out = list(ChunkPrefetcher(lambda x: x * 2, [1, 2, 3, 4]))
    assert out == [2, 4, 6, 8]

    def boom(x):
        if x == 2:
            raise ValueError("staged boom")
        return x

    it = iter(ChunkPrefetcher(boom, [1, 2, 3]))
    assert next(it) == 1
    with pytest.raises(ValueError, match="staged boom"):
        next(it)


def test_chunk_prefetcher_close_releases_worker():
    """An abandoned consumer must not leave the worker parked on a full
    queue holding staged chunks."""
    pf = ChunkPrefetcher(lambda x: x, list(range(10)), depth=1)
    assert next(iter(pf)) == 0
    pf.close()
    pf._thread.join(timeout=5.0)
    assert not pf._thread.is_alive()


def test_engine_rejects_split_data_stores(small_world):
    """The chunked data plane gathers from ONE shared sample store; a
    client built over its own array must be rejected, not silently
    staged from client 0's data."""
    model, train, clients, test = small_world
    other = {k: np.array(v) for k, v in train.items()}
    rogue = build_clients(other, [clients[0].indices])
    with pytest.raises(ValueError, match="shared sample store"):
        FederatedSimulation(model, _fl(), clients[:-1] + rogue, test)


# -------------------------------------------------------- eval layer ----

def test_evaluator_matches_unbatched_reference(small_world):
    model, _, clients, test = small_world
    params = model.init(jax.random.PRNGKey(1))
    acc, loss = Evaluator(model, test, batch_size=512)(params)
    logits, _ = model.forward(params, test)
    lf = np.asarray(logits, np.float64)
    labels = np.asarray(test["label"])
    ref_acc = float(np.mean(np.argmax(lf, -1) == labels))
    logz = np.log(np.sum(np.exp(lf - lf.max(-1, keepdims=True)), -1)) \
        + lf.max(-1)
    ref_loss = float(np.mean(logz - lf[np.arange(len(labels)), labels]))
    assert acc == pytest.approx(ref_acc, abs=1e-6)
    assert loss == pytest.approx(ref_loss, rel=1e-5)


def test_evaluator_batch_split_invariant(small_world):
    """Sum-based accumulation: accuracy/loss independent of the batch
    split (incl. a split that needs wrap-padding)."""
    model, _, clients, test = small_world
    params = model.init(jax.random.PRNGKey(2))
    a1, l1 = Evaluator(model, test, batch_size=512)(params)
    a2, l2 = Evaluator(model, test, batch_size=17)(params)
    assert a1 == pytest.approx(a2, abs=1e-6)
    assert l1 == pytest.approx(l2, rel=1e-5)


# ------------------------------------------------- checkpoint / resume ----

@pytest.mark.parametrize("algo,md", [("async_ama", 3), ("fedopt", 0)])
def test_save_restore_continue_bit_identical(small_world, tmp_path, algo,
                                             md):
    """Full round-state checkpoint {params, t, aux} (ring buffer /
    fedopt moments): save -> restore -> continue == uninterrupted run,
    bit-identically, even across different chunk boundaries."""
    model, _, clients, test = small_world
    fl = _fl(algorithm=algo, max_delay=md, p_delay=0.4 if md else 0.0)
    path = str(tmp_path / "state.npz")

    full = FederatedSimulation(model, fl, clients, test)
    hist_full = full.run(rounds=5, eval_every=2)

    part = FederatedSimulation(model, fl, clients, test)
    part.run(rounds=3, eval_every=2)
    part.save(path)

    cont = FederatedSimulation(model, fl, clients, test)
    cont.resume(path)
    assert cont.t == 3
    hist_cont = cont.run(rounds=2, eval_every=2)

    assert_states_identical(full.state, cont.state)
    assert hist_full.train_loss[3:] == hist_cont.train_loss
    # chunk boundaries sit on ABSOLUTE multiples of eval_every: the
    # resumed run evaluates at the same global rounds (here t=4) and
    # sees the same metrics as the uninterrupted run
    assert hist_cont.test_acc == hist_full.test_acc[1:]
    assert hist_cont.test_loss == hist_full.test_loss[1:]


# ------------------------------------------------------------ sharding ----

def test_engine_under_fl_mesh_bit_identical(small_world):
    """engine_mesh re-views whatever devices exist as (client, dsub,
    model); at CPU scale the constraints are degenerate and the result
    bit-identical to the mesh-free run."""
    model, _, clients, test = small_world
    mesh = engine_mesh(4)
    assert tuple(mesh.axis_names) == ("client", "dsub", "model")
    fl = _fl(algorithm="ama")
    plain = FederatedSimulation(model, fl, clients, test)
    meshed = FederatedSimulation(model, fl, clients, test, mesh=mesh)
    plain.run(rounds=2)
    meshed.run(rounds=2)
    assert_states_identical(plain.state, meshed.state)


# -------------------------------------------------------- public API ----

def test_run_round_and_eval_compat(small_world):
    """The legacy surface survives: run_round advances one round,
    evaluate returns (acc, loss), params/t/aux mirror the state."""
    model, _, clients, test = small_world
    sim = FederatedSimulation(model, _fl(), clients, test)
    tl = sim.run_round()
    assert np.isfinite(tl) and sim.t == 1
    acc, loss = sim.evaluate()
    assert 0.0 <= acc <= 1.0 and np.isfinite(loss)
    assert sim.params is sim.state["params"]
    assert sim.aux == sim.state["aux"]
