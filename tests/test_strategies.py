"""Server-strategy subsystem: registry, every rule's server paths against
its plain statement (tests/plain_server.py), the fedopt extension point,
and the fused scan engine vs the sequential per-round loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.configs.registry import ARCHS
from repro.core import strategies
from repro.core.round import init_state, make_round_step, make_train_loop
from repro.models.api import build_model
from plain_server import SCHEDULES, PlainServer, schedule


def tree(rng, C=None):
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    if C is None:
        return {"a": f(3, 4), "b": {"c": f(5)}}
    return {"a": f(C, 3, 4), "b": {"c": f(C, 5)}}


def sched_for(rng, C, max_delay=0):
    delayed = rng.rand(C) < 0.4
    delays = np.where(delayed, rng.randint(1, max(max_delay, 1) + 1, C), 1)
    return {"limited": jnp.asarray(rng.rand(C) < 0.5),
            "delayed": jnp.asarray(delayed),
            "delays": jnp.asarray(delays.astype(np.int32)),
            "data_sizes": jnp.asarray(rng.rand(C) + 0.5, jnp.float32)}


def assert_trees_close(got, want, **kw):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6, **kw)


# ------------------------------------------------------------ registry ----

def test_registry_names_and_resolve():
    assert {"ama", "ama_fes", "async_ama", "fedavg", "fedprox",
            "fedopt"} <= set(strategies.names())
    assert isinstance(strategies.resolve(FLConfig(algorithm="ama_fes")),
                      strategies.AMAStrategy)
    # the seed's implicit upgrade: ama + delays -> async ama
    s = strategies.resolve(FLConfig(algorithm="ama_fes", max_delay=5))
    assert isinstance(s, strategies.AsyncAMAStrategy)
    assert isinstance(strategies.resolve(FLConfig(algorithm="fedopt")),
                      strategies.FedOptStrategy)
    with pytest.raises(KeyError):
        strategies.get("nope")


def test_no_dispatch_chains_left():
    """Acceptance: algorithm dispatch has exactly one home (the registry)."""
    import repro.core.client
    import repro.core.round
    import repro.core.simulation
    import repro.launch.train
    for mod in (repro.core.round, repro.core.simulation, repro.launch.train,
                repro.core.client):
        with open(mod.__file__) as f:
            assert "fl.algorithm ==" not in f.read(), mod.__name__


# ------------------------------------ every rule vs its plain statement ----

RULES = [("ama", 0), ("async_ama", 3), ("fedavg", 0), ("fedprox", 0),
         ("fedopt", 0)]
@pytest.mark.parametrize("kind", SCHEDULES)
@pytest.mark.parametrize("algo,md", RULES)
def test_fused_server_update_matches_plain_reference(algo, md, kind):
    """Three rounds of each rule's fused server plane against the plain
    per-leaf statement of the rule (tests/plain_server.py): params stay
    within f32 rounding of the float64 reference."""
    rng = np.random.RandomState(11)
    fl = FLConfig(algorithm=algo, max_delay=md, p_delay=0.4 if md else 0.0,
                  alpha0=0.2, eta=0.05)
    strat = strategies.resolve(fl)
    plain = PlainServer(fl)
    prev = want = tree(rng)
    aux = strat.init_state(prev)
    for t in range(3):
        cp = tree(rng, C=4)
        sched = schedule(rng, 4, kind, max_delay=md)
        prev, aux = strat.fused_server_update(t, prev, cp, sched, aux)
        want = plain.step(t, want, cp, sched)
        assert_trees_close(prev, want, err_msg=f"round {t}")


def _bf16_wire(prev, cp):
    """What a bf16 uplink delivers: each client's delta rounded to bf16."""
    return jax.tree.map(
        lambda p, x: p + (x - p).astype(jnp.bfloat16).astype(jnp.float32),
        prev, cp)


@pytest.mark.parametrize("path", ["fused", "reduced", "compressed"])
@pytest.mark.parametrize("algo", ["ama", "fedavg", "fedprox"])
def test_mix_paths_match_plain_reference(algo, path):
    """Each server path of the mix family — the fused plane, the
    pre-reduced update the round takes on a sharded client axis
    (``client_reduce="force"``), and the update that consumes a bf16
    uplink's payload — against the plain statement of the rule."""
    from repro import comm
    rng = np.random.RandomState(12)
    fl = FLConfig(algorithm=algo, alpha0=0.2, eta=0.05,
                  client_reduce="force" if path == "reduced" else "off",
                  comm_plane="bf16" if path == "compressed" else "none")
    strat = strategies.resolve(fl)
    prev, cp = tree(rng), tree(rng, C=4)
    sched = schedule(rng, 4, "mixed")
    if path == "fused":
        got, _ = strat.fused_server_update(3, prev, cp, sched, {})
    elif path == "reduced":
        got, _ = strat.reduced_server_update(3, prev, cp, sched, {})
    else:
        plane = comm.resolve(fl)
        groups, _ = plane.compress(3, prev, cp, plane.init_residual(prev, 4))
        got, _ = strat.compressed_server_update(3, prev, groups, sched, {})
        cp = _bf16_wire(prev, cp)
    assert_trees_close(got, PlainServer(fl).step(3, prev, cp, sched))


# ------------------------------------------------ fedopt extension point ----

def test_fedopt_aggregates_and_carries_moments():
    rng = np.random.RandomState(4)
    fl = FLConfig(algorithm="fedopt", server_lr=0.1)
    strat = strategies.resolve(fl)
    prev = tree(rng)
    aux = strat.init_state(prev)
    assert int(aux["step"]) == 0
    p1, aux = strat.fused_server_update(0, prev, tree(rng, C=4),
                                        sched_for(rng, 4), aux)
    p2, aux = strat.fused_server_update(1, p1, tree(rng, C=4),
                                        sched_for(rng, 4), aux)
    assert int(aux["step"]) == 2
    assert any(float(jnp.max(jnp.abs(l))) > 0
               for l in jax.tree.leaves(aux["m"]))
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         p2, prev)
    assert max(jax.tree.leaves(moved)) > 0
    for l in jax.tree.leaves(p2):
        assert np.all(np.isfinite(np.asarray(l)))


def test_fedopt_first_step_is_sign_like_adam():
    """With zero init moments, step 1 is lr * delta/(|delta| + tau) (bias
    correction cancels): bounded by server_lr in magnitude."""
    rng = np.random.RandomState(5)
    fl = FLConfig(algorithm="fedopt", server_lr=0.05)
    strat = strategies.resolve(fl)
    prev = tree(rng)
    cp = tree(rng, C=3)
    sched = {"limited": jnp.zeros((3,), bool),
             "delayed": jnp.zeros((3,), bool),
             "delays": jnp.ones((3,), jnp.int32),
             "data_sizes": jnp.ones((3,), jnp.float32)}
    p1, _ = strat.fused_server_update(0, prev, cp, sched,
                                      strat.init_state(prev))
    step = jax.tree.map(lambda a, b: np.abs(np.asarray(a - b)), p1, prev)
    assert max(float(s.max()) for s in jax.tree.leaves(step)) <= 0.05 + 1e-6


# -------------------------------------------------- fused scan vs loop ----

@pytest.mark.parametrize("algo,md", [("ama_fes", 0), ("ama_fes", 3),
                                     ("fedavg", 0), ("fedprox", 0),
                                     ("fedopt", 0)])
def test_scan_engine_matches_sequential_rounds(algo, md):
    """One lax.scan over 5 rounds == 5 sequential round_step calls."""
    n_rounds, C, steps, b = 5, 2, 2, 4
    model = build_model(ARCHS["paper-cnn"])
    fl = FLConfig(algorithm=algo, max_delay=md, p_delay=0.4 if md else 0.0,
                  lr=0.05)
    rng = np.random.RandomState(7)
    batches = {
        "image": jnp.asarray(rng.randn(n_rounds, C, steps, b, 28, 28, 1),
                             jnp.float32),
        "label": jnp.asarray(rng.randint(0, 10, (n_rounds, C, steps, b)),
                             jnp.int32)}
    scheds = {
        "limited": jnp.asarray(rng.rand(n_rounds, C) < 0.5),
        "delayed": jnp.asarray(rng.rand(n_rounds, C) < (0.4 if md else 0.0)),
        "delays": jnp.asarray(
            rng.randint(1, max(md, 1) + 1, (n_rounds, C)), jnp.int32),
        "data_sizes": jnp.asarray(rng.rand(n_rounds, C) + 0.5, jnp.float32)}

    state0 = init_state(model, fl, jax.random.PRNGKey(0))
    step = jax.jit(make_round_step(model, fl))
    state_seq = state0
    seq_losses = []
    for r in range(n_rounds):
        state_seq, metrics = step(state_seq,
                                  jax.tree.map(lambda x: x[r], batches),
                                  jax.tree.map(lambda x: x[r], scheds))
        seq_losses.append(float(metrics["loss"]))

    loop = make_train_loop(model, fl, per_round_batch=True, donate=False)
    state_scan, metrics = loop(init_state(model, fl, jax.random.PRNGKey(0)),
                               batches, scheds)

    assert int(state_scan["t"]) == n_rounds
    np.testing.assert_allclose(np.asarray(metrics["loss"]),
                               np.asarray(seq_losses), rtol=1e-5, atol=1e-6)
    for g, w in zip(jax.tree.leaves(state_scan["params"]),
                    jax.tree.leaves(state_seq["params"])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
    for g, w in zip(jax.tree.leaves(state_scan["aux"]),
                    jax.tree.leaves(state_seq["aux"])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
