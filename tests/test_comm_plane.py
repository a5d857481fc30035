"""The compressed communication plane (repro.comm + fused server kernels).

Five nets, mirroring the plane's layering:

  * codec units — registry/resolve contract, nominal wire fractions,
    exact payload byte accounting (topk < q8 < bf16 < dense);
  * kernel parity — the fused dequantize-accumulate Pallas body
    (``server_mix_delta_flat``: int8 AND bf16 payloads, and top-k pairs
    densified into it) against the jnp oracles in interpret mode:
    padding path, K=1 edge;
  * fused == densify — ``server_mix_compressed_tree`` must equal
    reconstruct-then-dense-mix for every payload kind (the strategies'
    ``compressed_server_update`` is only a dispatch around this);
  * engine — scan == loop bit-identity WITH compression + error-feedback
    residual aux for all five strategies, resume-tail bit-identity with
    ``aux["comm"]`` in the checkpoint, and the ``comm_plane="none"``
    structural no-op (no comm aux, wire fraction 1, dense bytes);
  * telemetry/CI plumbing — compressed-wire round metrics, the
    bandwidth env consuming the wire fraction (compression raises
    on-time participation), and ``check_metrics.py --require-comm``.

Property-based versions of the codec bounds (hypothesis-gated, nightly)
live in tests/test_comm_properties.py.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro import env as env_mod
from repro.comm.plane import Q8Plane, TopKPlane, decode
from repro.configs.base import FLConfig
from repro.configs.registry import ARCHS
from repro.core.simulation import FederatedSimulation
from repro.data.partition import shard_partition
from repro.data.pipeline import build_clients
from repro.data.synth import make_image_classification
from repro.kernels import ref
from repro.kernels.server_plane import (server_mix_compressed_tree,
                                        server_mix_delta_flat,
                                        server_mix_tree)
from repro.models.api import build_model
from repro.obs.log import MetricsLogger

ROOT = os.path.join(os.path.dirname(__file__), "..")

TOL = dict(rtol=2e-6, atol=2e-6)


@pytest.fixture(scope="module")
def small_world():
    train, test = make_image_classification(n_train=240, n_test=60, seed=0)
    clients = build_clients(train, shard_partition(train["label"], 8, seed=0))
    model = build_model(ARCHS["paper-cnn"])
    return model, clients, test


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


# ---------------------------------------------------------- codec units ----

def test_registry_and_resolve_contract():
    assert {"bf16", "q8", "int8", "topk"} <= set(comm.names())
    assert comm.resolve(_fl()) is None                 # dense default
    assert comm.resolve(_fl(comm_plane="none")) is None
    assert isinstance(comm.resolve(_fl(comm_plane="q8")), Q8Plane)
    assert isinstance(comm.resolve(_fl(comm_plane="int8")), Q8Plane)
    assert isinstance(comm.resolve(_fl(comm_plane="topk")), TopKPlane)
    with pytest.raises(ValueError, match="unknown comm plane"):
        comm.resolve(_fl(comm_plane="zip"))
    with pytest.raises(ValueError, match="comm_topk_frac"):
        comm.resolve(_fl(comm_plane="topk", comm_topk_frac=0.0))


def test_nominal_wire_fractions():
    assert comm.wire_fraction(_fl()) == 1.0
    assert comm.wire_fraction(_fl(comm_plane="bf16")) == 0.5
    assert comm.wire_fraction(_fl(comm_plane="q8")) == 0.25
    assert comm.wire_fraction(
        _fl(comm_plane="topk", comm_topk_frac=0.05)) == pytest.approx(0.1)
    # value+index pairs stop paying off past frac = 1/2
    assert comm.wire_fraction(
        _fl(comm_plane="topk", comm_topk_frac=0.9)) == 1.0


def test_payload_bytes_ordering(small_world):
    model, _, _ = small_world
    params = model.init(jax.random.PRNGKey(0))
    dense = comm.dense_bytes(params)
    by = {p: comm.resolve(_fl(comm_plane=p, comm_topk_frac=0.01))
          .payload_bytes(params) for p in ("bf16", "q8", "topk")}
    assert by["topk"] < by["q8"] < by["bf16"] < dense
    assert by["bf16"] * 2 == dense                    # f32 model: exactly 2x
    # q8 = 1 byte/param + one f32 scale word per dtype group
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    assert n_params <= by["q8"] <= n_params + 4 * len(jax.tree.leaves(params))


def test_codec_roundtrip_and_error_feedback_algebra():
    """One compress() pass per plane on a toy tree: decode(payload) + new
    residual telescopes back to the exact dense error, and q8 honours
    its elementwise bound."""
    rng = np.random.RandomState(7)
    prev = {"w": jnp.asarray(rng.randn(13, 5), jnp.float32),
            "b": jnp.asarray(rng.randn(5), jnp.float32)}
    K = 3
    stacked = jax.tree.map(
        lambda p: p[None] + jnp.asarray(
            rng.randn(K, *p.shape) * 0.1, jnp.float32), prev)
    n = 13 * 5 + 5
    # dense flat delta in canonical leaf order (tree.leaves order)
    leaves_p = jax.tree.leaves(prev)
    leaves_s = jax.tree.leaves(stacked)
    d_dense = np.concatenate(
        [np.asarray(s.reshape(K, -1) - p.reshape(-1)[None])
         for p, s in zip(leaves_p, leaves_s)], axis=1)
    for name in ("bf16", "q8", "topk"):
        plane = comm.resolve(_fl(comm_plane=name, comm_topk_frac=0.1))
        res0 = plane.init_residual(prev, K)
        assert set(res0) == {"g0"} and res0["g0"].shape == (K, n)
        groups, res1 = plane.compress(0, prev, stacked, res0)
        assert len(groups) == 1
        dq = np.asarray(decode(groups[0][1], n))
        # EF telescoping: dq + residual == dense delta (float32 algebra)
        np.testing.assert_allclose(dq + np.asarray(res1["g0"]), d_dense,
                                   rtol=1e-5, atol=1e-6)
        if name == "q8":
            scale = np.asarray(groups[0][1]["scale"])
            assert np.all(np.abs(d_dense - dq) <= scale[:, None] * (1 + 1e-6))
        if name == "topk":
            kk = plane._kk(n)
            assert groups[0][1]["v"].shape == (K, kk)
            assert np.count_nonzero(dq, axis=1).max() <= kk
    # error feedback off: no residual state at all
    plane = comm.resolve(_fl(comm_plane="q8", comm_error_feedback=False))
    assert plane.init_residual(prev, K) == {}
    groups, res = plane.compress(0, prev, stacked, {})
    assert res == {} and len(groups) == 1


def test_q8_stochastic_rounding_pure_in_round_index():
    """Same (t, inputs) -> bit-identical payload; different t -> a
    different draw (the scan == resume determinism contract)."""
    rng = np.random.RandomState(0)
    prev = {"w": jnp.asarray(rng.randn(64), jnp.float32)}
    stacked = {"w": prev["w"][None] + jnp.asarray(
        rng.randn(2, 64) * 0.1, jnp.float32)}
    plane = comm.resolve(_fl(comm_plane="q8"))
    (g1,), _ = plane.compress(3, prev, stacked, {})
    (g2,), _ = plane.compress(3, prev, stacked, {})
    (g3,), _ = plane.compress(4, prev, stacked, {})
    np.testing.assert_array_equal(np.asarray(g1[1]["d"]),
                                  np.asarray(g2[1]["d"]))
    assert not np.array_equal(np.asarray(g1[1]["d"]),
                              np.asarray(g3[1]["d"]))


# -------------------------------------------------------- kernel parity ----

def _mix_world(rng, K, N):
    return dict(prev=jnp.asarray(rng.randn(N), jnp.float32),
                sizes=jnp.asarray(rng.rand(K) + 0.5, jnp.float32),
                keep=jnp.asarray((rng.rand(K) < 0.7).astype(np.float32)),
                coefs=jnp.asarray([0.1, 2.5e-3, 0.95, 7.0], jnp.float32))


@pytest.mark.parametrize("N,block", [(4096, 1024), (4096 + 17, 1024),
                                     (100, 1024)])  # padding / block > N
@pytest.mark.parametrize("K", [1, 7])
@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.bfloat16])
def test_mix_delta_kernel_matches_oracle(N, block, K, qdtype):
    """Fused dequantize-accumulate: int8 and bf16 compressed rows upcast
    inside the kernel tile == the jnp oracle's math."""
    rng = np.random.RandomState(N + K)
    w = _mix_world(rng, K, N)
    if qdtype == jnp.int8:
        d = jnp.asarray(rng.randint(-127, 128, (K, N)), jnp.int8)
        rowscale = jnp.asarray(rng.rand(K) * 1e-2 + 1e-4, jnp.float32)
    else:
        d = jnp.asarray(rng.randn(K, N), jnp.bfloat16)
        rowscale = jnp.ones((K,), jnp.float32)
    got = server_mix_delta_flat(w["prev"], d, rowscale, w["sizes"],
                                w["keep"], w["coefs"], block=block,
                                interpret=True)
    want = ref.server_mix_delta_math(w["prev"], d, rowscale, w["sizes"],
                                     w["keep"], w["coefs"])
    assert got.dtype == w["prev"].dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("N,block", [(2048, 512), (2048 + 31, 512)])
@pytest.mark.parametrize("K", [1, 6])
def test_mix_scatter_kernel_matches_oracle(N, block, K):
    """Top-k plane: the (value, position) pairs densified into the
    delta kernel — the top-k route on every backend — equal the
    scatter oracle, incl. positions landing in the padded tail tile."""
    rng = np.random.RandomState(N + K)
    w = _mix_world(rng, K, N)
    kk = 37
    idx = jnp.asarray(np.stack([rng.choice(N, kk, replace=False)
                                for _ in range(K)]), jnp.int32)
    vals = jnp.asarray(rng.randn(K, kk), jnp.float32)
    groups = [([0], {"kind": "topk", "v": vals, "i": idx})]
    got = server_mix_compressed_tree(w["prev"], groups, w["sizes"],
                                     w["keep"], w["coefs"],
                                     impl="interpret", block=block)
    want = ref.server_mix_scatter_math(w["prev"], vals, idx, w["sizes"],
                                       w["keep"], w["coefs"])
    assert got.dtype == w["prev"].dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# ------------------------------------------------------ fused == densify ----

@pytest.mark.parametrize("plane_name", ["bf16", "q8", "topk"])
def test_compressed_tree_matches_reconstruct_then_dense_mix(small_world,
                                                            plane_name):
    """server_mix_compressed_tree(groups) == dense mix over the plane's
    own reconstruction — on both the oracle and the interpret kernel
    path. This is the invariant that makes the strategies' densify
    fallback and the fused hook interchangeable."""
    model, _, _ = small_world
    prev = model.init(jax.random.PRNGKey(3))
    K = 4
    rng = np.random.RandomState(11)
    stacked = jax.tree.map(
        lambda p: p[None] + jnp.asarray(
            rng.randn(K, *p.shape) * 0.05, p.dtype), prev)
    plane = comm.resolve(_fl(comm_plane=plane_name, comm_topk_frac=0.05))
    groups, _ = plane.compress(2, prev, stacked, {})
    sizes = jnp.asarray(rng.rand(K) + 0.5, jnp.float32)
    keep = jnp.asarray((rng.rand(K) < 0.75).astype(np.float32))
    coefs = jnp.asarray([0.1, 2.5e-3, 0.95, 5.0], jnp.float32)
    recon = plane.reconstruct(prev, groups)
    want = server_mix_tree(prev, recon, sizes, keep, coefs, impl="ref")
    for impl in ("ref", "interpret"):
        got = server_mix_compressed_tree(prev, groups, sizes, keep, coefs,
                                         impl=impl)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **TOL)


# ---------------------------------------------------------------- engine ----

ENGINE_CASES = [("ama", "q8"), ("async_ama", "q8"), ("fedavg", "q8"),
                ("fedprox", "q8"), ("fedopt", "q8"),
                ("ama", "topk"), ("fedavg", "bf16")]


@pytest.mark.parametrize("algo,plane", ENGINE_CASES)
def test_chunked_scan_bit_identical_with_compression(small_world, algo,
                                                     plane):
    """All five strategies under q8 (fused mix family + densify
    fallbacks) and the other planes on a representative each: the
    chunked-scan engine == the per-round loop bit-identically, with the
    error-feedback residual riding aux["comm"]."""
    model, clients, test = small_world
    md = 3 if algo == "async_ama" else 0
    fl = _fl(algorithm=algo, comm_plane=plane, comm_topk_frac=0.05,
             max_delay=md, p_delay=0.4 if md else 0.0)
    sims = {s: FederatedSimulation(model, fl, clients, test, use_scan=s)
            for s in (True, False)}
    hists = {s: sim.run(rounds=3, eval_every=3) for s, sim in sims.items()}
    assert_states_identical(sims[True].state, sims[False].state)
    assert hists[True].train_loss == hists[False].train_loss
    assert hists[True].test_acc == hists[False].test_acc
    aux = sims[True].state["aux"]
    assert "comm" in aux
    res = aux["comm"]["g0"]
    assert res.shape[0] == fl.clients_per_round
    assert res.dtype == jnp.float32
    # every plane leaves a nonzero residual after a real round (for
    # bf16 it is the dropped low mantissa bits of the f32 deltas)
    assert float(jnp.max(jnp.abs(res))) > 0.0


def test_resume_tail_bit_identical_with_residual_aux(small_world, tmp_path):
    """The checkpoint carries aux["comm"]: save -> restore -> continue
    == uninterrupted, bit-identically, under q8 + error feedback (the
    residual AND the stochastic-rounding stream both replay)."""
    model, clients, test = small_world
    fl = _fl(algorithm="ama", comm_plane="q8")
    path = str(tmp_path / "state.npz")

    full = FederatedSimulation(model, fl, clients, test)
    hist_full = full.run(rounds=5, eval_every=2)

    part = FederatedSimulation(model, fl, clients, test)
    part.run(rounds=3, eval_every=2)
    part.save(path)

    cont = FederatedSimulation(model, fl, clients, test)
    cont.resume(path)
    assert cont.t == 3
    assert "comm" in cont.state["aux"]
    hist_cont = cont.run(rounds=2, eval_every=2)

    assert_states_identical(full.state, cont.state)
    assert hist_full.train_loss[3:] == hist_cont.train_loss
    assert hist_cont.test_acc == hist_full.test_acc[1:]


def test_none_plane_is_structurally_dense(small_world):
    """comm_plane="none" resolves to no plane at all: no aux["comm"],
    dense wire fraction/bytes, compression_ratio exactly 1.0 — the
    engine's pre-comm program, untouched. With comm_error_feedback off,
    compressed planes also carry no residual state."""
    model, clients, test = small_world
    sim = FederatedSimulation(model, _fl(), clients, test)
    sim.run(rounds=2, eval_every=2)
    assert "comm" not in sim.state["aux"]

    sim_nf = FederatedSimulation(
        model, _fl(comm_plane="q8", comm_error_feedback=False), clients,
        test)
    sim_nf.run(rounds=2, eval_every=2)
    assert "comm" not in sim_nf.state["aux"]


# ----------------------------------------------------- telemetry + env ----

def test_round_metrics_carry_compressed_wire_fields(small_world):
    """Extended round rows: bytes_on_wire_compressed charges the ACTUAL
    q8 payload (~4x less than dense) and compression_ratio is the
    static dense/compressed ratio; the dense plane reports exactly 1.0
    with compressed == bytes_on_wire."""
    model, clients, test = small_world
    rows = {}
    for plane in ("none", "q8"):
        fl = _fl(algorithm="ama", comm_plane=plane, extended_metrics=True)
        logger = MetricsLogger(None)
        FederatedSimulation(model, fl, clients, test,
                            logger=logger).run(rounds=2, eval_every=2)
        rows[plane] = [r for r in logger.rows if r["kind"] == "round"]
    params = model.init(jax.random.PRNGKey(0))
    dense = comm.dense_bytes(params)
    per_client = comm.resolve(
        _fl(comm_plane="q8")).payload_bytes(params)
    for r in rows["none"]:
        assert r["compression_ratio"] == 1.0
        assert r["bytes_on_wire_compressed"] == r["bytes_on_wire"]
    for r in rows["q8"]:
        assert r["compression_ratio"] == pytest.approx(
            dense / per_client, rel=1e-6)
        assert r["bytes_on_wire_compressed"] == pytest.approx(
            r["n_on_time"] * per_client)
        assert r["bytes_on_wire_compressed"] < r["bytes_on_wire"]


def test_bandwidth_env_consumes_wire_fraction():
    """The bandwidth env's deadline check prices the COMPRESSED upload:
    q8 strictly raises on-time participation over dense at a deadline
    that dense mostly misses (the paper's delay-tolerance-vs-compression
    effect), and the plane leaves the delay distribution's support
    unchanged."""
    on_time = {}
    for plane in ("none", "q8"):
        fl = _fl(comm_plane=plane, env="bandwidth", max_delay=5,
                 bw_upload_mbits=16.0, bw_mean_mbps=4.0, bw_sigma=0.8,
                 bw_deadline_s=1.0)
        sb = env_mod.resolve(fl).batch(0, 200)
        on_time[plane] = float(np.mean(~np.asarray(sb["delayed"], bool)))
    assert on_time["q8"] > on_time["none"]


def test_check_metrics_require_comm(tmp_path):
    """scripts/check_metrics.py --require-comm: exit 0 on rows with real
    compression, exit 1 when the wire fields are missing or the ratio
    never exceeds 1 (a plane that silently ships dense bytes); plain
    validation still accepts schema-2 files without the new fields."""
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    script = os.path.join(ROOT, "scripts", "check_metrics.py")

    def jsonl(name, rows):
        p = tmp_path / name
        p.write_text("".join(json.dumps(r) + "\n" for r in rows))
        return str(p)

    def rnd(t, **kw):
        return {"kind": "round", "t": t, "loss": 1.0, "n_on_time": 4,
                "bytes_on_wire": 800.0, **kw}

    hdr = {"kind": "header", "schema": 3}
    good = jsonl("good.jsonl", [
        hdr, rnd(1, bytes_on_wire_compressed=204.0, compression_ratio=3.92),
        rnd(2, bytes_on_wire_compressed=204.0, compression_ratio=3.92)])
    missing = jsonl("missing.jsonl", [hdr, rnd(1), rnd(2)])
    dense = jsonl("dense.jsonl", [
        hdr, rnd(1, bytes_on_wire_compressed=800.0, compression_ratio=1.0),
        rnd(2, bytes_on_wire_compressed=800.0, compression_ratio=1.0)])
    v2 = jsonl("v2.jsonl", [{"kind": "header", "schema": 2}, rnd(1)])

    def run(*argv):
        return subprocess.run([sys.executable, script, *argv],
                              capture_output=True, text=True, env=env)

    assert run(good, "--require-comm").returncode == 0
    r = run(missing, "--require-comm")
    assert r.returncode == 1 and "comm series" in r.stdout
    r = run(dense, "--require-comm")
    assert r.returncode == 1 and "not actually compressing" in r.stdout
    assert run(missing).returncode == 0      # fields are optional sans flag
    assert run(v2).returncode == 0           # schema-2 files stay valid
