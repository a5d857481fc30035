"""The federation telemetry plane (repro.obs).

The contracts the observability PR must keep:

  * enabling ``fl.extended_metrics`` NEVER changes the params stream —
    metrics-on == metrics-off bit-identically, on the fused scan AND
    the per-round fallback, and the two engines agree on the metric
    series themselves;
  * a resumed run's JSONL round/eval rows are the exact tail of the
    uninterrupted run's file (the log analogue of checkpoint
    bit-identity; header/phases rows are wall-clock and excluded);
  * ``History.final_accuracy`` / ``stability_variance`` window by
    ROUNDS, not eval points (the seed's ``eval_every > 1`` unit bug),
    and the report CLI reproduces them exactly from the file alone;
  * the JSONL schema is validated (``validate_rows`` /
    scripts/check_metrics.py — the CI gate on launcher output).
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.configs.registry import ARCHS
from repro.core.simulation import FederatedSimulation
from repro.data.partition import shard_partition
from repro.data.pipeline import build_clients
from repro.data.synth import make_image_classification
from repro.exec.engine import History
from repro.models.api import build_model
from repro.obs.log import (SCHEMA_VERSION, MetricsLogger, read_rows,
                           validate_rows)
from repro.obs.metrics import (ROUND_METRIC_KEYS, payload_bytes,
                               stability_stats, window_by_rounds)
from repro.obs.provenance import COMPARE_KEYS, diff, provenance
from repro.obs.timing import PhaseTimes, sync_time

ROOT = os.path.join(os.path.dirname(__file__), "..")


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


ALGOS = [("ama", 0), ("async_ama", 3), ("fedprox", 0)]


def assert_states_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------- metrics bit-identity net ----

@pytest.mark.parametrize("algo,md", ALGOS)
def test_extended_metrics_never_change_params(small_world, algo, md):
    """fl.extended_metrics on vs off, scan vs per-round: all four runs
    produce bit-identical params/aux, and the scan and no-scan engines
    agree on every extended metric series."""
    model, clients, test = small_world
    sims, rows = {}, {}
    for ext in (False, True):
        for scan in (True, False):
            fl = _fl(algorithm=algo, max_delay=md,
                     p_delay=0.4 if md else 0.0, extended_metrics=ext)
            logger = MetricsLogger(None) if ext else None
            sim = FederatedSimulation(model, fl, clients, test,
                                      use_scan=scan, logger=logger)
            sim.run(rounds=4, eval_every=2)
            sims[ext, scan] = sim
            if ext:
                rows[scan] = [r for r in logger.rows
                              if r["kind"] == "round"]
    ref = sims[False, True].state
    for key, sim in sims.items():
        assert_states_identical(ref, sim.state)
    # the two engines log the identical extended series
    assert len(rows[True]) == len(rows[False]) == 4
    for ra, rb in zip(rows[True], rows[False]):
        assert set(ROUND_METRIC_KEYS) <= set(ra)
        assert ra == rb


def test_round_metric_semantics(small_world):
    """Spot-check the series against hand-computable facts: alpha_eff
    follows Eq. 5 for sync AMA, bytes_on_wire = on-time x payload,
    stale_hist counts exactly the delayed cohorts."""
    model, clients, test = small_world
    fl = _fl(algorithm="ama", extended_metrics=True)
    logger = MetricsLogger(None)
    sim = FederatedSimulation(model, fl, clients, test, logger=logger)
    sim.run(rounds=4, eval_every=2)
    payload = payload_bytes(sim.params)
    rnd = [r for r in logger.rows if r["kind"] == "round"]
    for r in rnd:
        # row t counts COMPLETED rounds (1-indexed); Eq. 5's round
        # index is the 0-indexed t the round entered with
        want = min(fl.alpha0 + fl.eta * (r["t"] - 1), fl.alpha_cap)
        assert r["alpha_eff"] == pytest.approx(want, abs=1e-7)
        assert r["bytes_on_wire"] == pytest.approx(
            r["n_on_time"] * payload)
        assert len(r["stale_hist"]) == fl.max_delay + 1
        assert sum(r["stale_hist"]) == r["n_delayed"]
        assert r["n_on_time"] + r["n_delayed"] == fl.clients_per_round


@pytest.mark.parametrize("algo,md", ALGOS)
def test_required_series_present_per_algorithm(small_world, algo, md):
    """ama / async_ama / fedprox all emit the full per-round staleness /
    participation / mix series (the acceptance's three algorithms)."""
    model, clients, test = small_world
    fl = _fl(algorithm=algo, max_delay=md, p_delay=0.4 if md else 0.0,
             extended_metrics=True)
    logger = MetricsLogger(None)
    FederatedSimulation(model, fl, clients, test,
                        logger=logger).run(rounds=2, eval_every=2)
    rnd = [r for r in logger.rows if r["kind"] == "round"]
    assert len(rnd) == 2
    for r in rnd:
        for k in ROUND_METRIC_KEYS + ("loss", "n_on_time", "t"):
            assert k in r, (algo, k)
    if algo == "fedprox":      # pure weighted average: no AMA mix
        assert all(r["alpha_eff"] == 0.0 for r in rnd)


# ------------------------------------------------ JSONL resume contract ----

def test_resume_produces_identical_jsonl_tail(small_world, tmp_path):
    """save -> restore -> continue logs round/eval rows bit-identical to
    the uninterrupted run's tail (header/phases rows are wall-clock and
    excluded from the contract)."""
    model, clients, test = small_world
    fl = _fl(algorithm="async_ama", max_delay=3, p_delay=0.4,
             extended_metrics=True)
    ckpt = str(tmp_path / "state.npz")

    full_log = MetricsLogger(None)
    full = FederatedSimulation(model, fl, clients, test, logger=full_log)
    full.run(rounds=6, eval_every=2)

    part = FederatedSimulation(model, fl, clients, test)
    part.run(rounds=4, eval_every=2)
    part.save(ckpt)

    cont_log = MetricsLogger(None)
    cont = FederatedSimulation(model, fl, clients, test, logger=cont_log)
    cont.resume(ckpt)
    cont.run(rounds=2, eval_every=2)

    def data_rows(log):
        return [r for r in log.rows if r["kind"] in ("round", "eval")]

    tail = [r for r in data_rows(full_log) if r["t"] > 4
            or (r["kind"] == "eval" and r["t"] > 4)]
    assert data_rows(cont_log) == tail
    header = cont_log.rows[0]
    assert header["kind"] == "header" and header["resumed_at"] == 4


# --------------------------------------- round-windowed stability math ----

def test_history_windows_by_rounds_not_eval_points():
    """eval_every=5 regression: stability_variance(last=20) must cover
    the evals of the last 20 ROUNDS (4 points), not the last 20 eval
    points (all 10, silently spanning 50 rounds — the seed bug)."""
    accs = [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0]
    h = History(test_acc=accs, eval_rounds=list(range(5, 55, 5)))
    s = stability_stats(h.eval_rounds, h.test_acc, last=20)
    assert s["n_evals"] == 4                      # rounds 35,40,45,50
    assert h.final_accuracy(last=20) == pytest.approx(np.mean(accs[-4:]))
    assert h.stability_variance(last=20) == pytest.approx(
        np.var(np.array(accs[-4:]) * 100.0))
    np.testing.assert_array_equal(
        window_by_rounds(h.eval_rounds, 20),
        np.array([False] * 6 + [True] * 4))
    # legacy History without round indices: counts eval points (old
    # behaviour is the only defensible reading of the data it has)
    legacy = stability_stats([], accs, last=4)
    assert legacy["n_evals"] == 4


def test_stability_stats_empty_window():
    s = stability_stats([], [], last=50)
    assert s["n_evals"] == 0
    assert np.isnan(s["final_accuracy"])


# ----------------------------------------------------- report CLI ----

@pytest.fixture(scope="module")
def logged_run(small_world, tmp_path_factory):
    """One paper-CNN run recorded to a real JSONL file + its in-process
    History (the exactness bridge the report must reproduce)."""
    model, clients, test = small_world
    path = str(tmp_path_factory.mktemp("obs") / "run.jsonl")
    fl = _fl(algorithm="ama", extended_metrics=True)
    with MetricsLogger(path) as logger:
        sim = FederatedSimulation(model, fl, clients, test, logger=logger)
        hist = sim.run(rounds=6, eval_every=2)
    return path, hist


def test_report_reproduces_history_exactly(logged_run):
    from repro.obs.report import history_from_rows, summarize
    path, hist = logged_run
    rows = read_rows(path)
    assert validate_rows(rows) == []
    h2 = history_from_rows(rows)
    assert h2.test_acc == hist.test_acc
    assert h2.train_loss == hist.train_loss
    assert h2.eval_rounds == hist.eval_rounds == [2, 4, 6]
    s = summarize(rows, last=4)
    # EXACT equality: same stability_stats on json-round-tripped floats
    assert s["final_accuracy"] == hist.final_accuracy(last=4)
    assert s["stability_variance"] == hist.stability_variance(last=4)
    assert s["rounds"] == 6 and s["algorithm"] == "ama"
    assert s["bytes_on_wire_total"] > 0
    assert "phases" in s


def test_report_cli_render_and_compare(logged_run, capsys):
    from repro.obs.report import main
    path, _ = logged_run
    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "accuracy:" in out and "staleness:" in out and "mix:" in out
    assert main(["--compare", path, path]) == 0
    out = capsys.readouterr().out
    assert "deltas (B - A)" in out
    assert "provenance mismatch" not in out     # same file, same env


def test_report_prints_cpu_counters_apart():
    """A ``*_cpu`` counter is CPU seconds: it takes no share of the
    phases' wall total and prints on a line of its own."""
    from repro.obs.report import render, summarize
    ph = {"stage": {"seconds": 3.0, "calls": 1},
          "eval": {"seconds": 1.0, "calls": 1},
          "stage_cpu": {"seconds": 2.0, "calls": 1}}
    out = render(summarize([{"kind": "phases", "phases": ph}]))
    assert "stage=3.00s(75%)" in out and "eval=1.00s(25%)" in out
    assert "cpu: stage_cpu=2.00s" in out and "stage_cpu=2.00s(" not in out


def test_report_prints_device_store_counter_apart():
    """``stage_device`` counts device-gathered chunks with zero
    seconds: it takes no share of the wall total and prints the chunks
    it counts on a line of its own."""
    from repro.obs.report import render, summarize
    ph = {"stage": {"seconds": 3.0, "calls": 4},
          "stage_gather": {"seconds": 1.0, "calls": 4},
          "stage_device": {"seconds": 0.0, "calls": 4}}
    out = render(summarize([{"kind": "phases", "phases": ph}]))
    assert "stage=3.00s(75%)" in out and "stage_gather=1.00s(25%)" in out
    assert "calls: stage_device=4" in out
    assert "stage_device=0.00s(" not in out


def test_report_cli_rejects_invalid_file(tmp_path):
    from repro.obs.report import main
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "round", "t": 0}) + "\n")
    with pytest.raises(SystemExit) as e:
        main([str(bad)])
    assert e.value.code == 2


# ------------------------------------------------- schema validation ----

def test_validate_rows_accepts_logger_output(small_world):
    model, clients, test = small_world
    logger = MetricsLogger(None)
    FederatedSimulation(model, _fl(extended_metrics=True), clients, test,
                        logger=logger).run(rounds=2, eval_every=2)
    assert validate_rows(logger.rows) == []
    assert logger.rows[0]["schema"] == SCHEMA_VERSION
    assert logger.rows[0]["payload_bytes"] > 0


def test_validate_rows_catches_violations():
    hdr = {"kind": "header", "schema": SCHEMA_VERSION}
    rnd = {"kind": "round", "t": 1, "loss": 1.0, "n_on_time": 4}
    assert validate_rows([]) != []
    assert any("header" in e for e in validate_rows([rnd]))
    assert any("schema" in e for e in
               validate_rows([{"kind": "header", "schema": 99}]))
    assert any("duplicate" in e for e in validate_rows([hdr, hdr]))
    assert any("unknown kind" in e for e in
               validate_rows([hdr, {"kind": "banana"}]))
    assert any("missing keys" in e for e in
               validate_rows([hdr, {"kind": "round", "t": 0}]))
    assert any("not after" in e for e in
               validate_rows([hdr, rnd, dict(rnd)]))
    assert any("beyond last" in e for e in validate_rows(
        [hdr, rnd, {"kind": "eval", "t": 9, "test_acc": .5,
                    "test_loss": 1.0}]))
    assert validate_rows(
        [hdr, rnd, {"kind": "eval", "t": 1, "test_acc": .5,
                    "test_loss": 1.0}]) == []


def test_check_metrics_script(logged_run, tmp_path):
    """scripts/check_metrics.py — the CI gate on launcher JSONL: exit 0
    + OK on a valid extended run, exit 1 on a schema violation."""
    path, _ = logged_run
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    script = os.path.join(ROOT, "scripts", "check_metrics.py")
    ok = subprocess.run([sys.executable, script, path,
                         "--require-extended"],
                        capture_output=True, text=True, env=env)
    assert ok.returncode == 0, ok.stderr
    assert "OK" in ok.stdout
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "round", "t": 0}) + "\n")
    fail = subprocess.run([sys.executable, script, str(bad)],
                          capture_output=True, text=True, env=env)
    assert fail.returncode == 1


# ------------------------------------------------- timing + provenance ----

def test_phase_times_accumulate_and_sync():
    pt = PhaseTimes()
    with pt.phase("eval") as span:
        span.sync(jax.numpy.ones(4) * 2)
    with pt.phase("eval"):
        pass
    pt.add("stage", 0.5)
    s = pt.summary()
    assert s["eval"]["calls"] == 2 and s["eval"]["seconds"] >= 0
    assert s["stage"] == {"seconds": 0.5, "calls": 1}
    assert pt.total() >= 0.5
    dt, out = sync_time(lambda x: x + 1, jax.numpy.zeros(3))
    assert dt >= 0 and float(out[0]) == 1.0


def test_engine_populates_phase_timer(small_world):
    """A run books compile (first chunk-length specialisation), stage
    and eval phases; a second same-shape chunk books steady-state
    dispatch, not compile."""
    model, clients, test = small_world
    sim = FederatedSimulation(model, _fl(), clients, test)
    sim.run(rounds=4, eval_every=2)
    s = sim.timer.summary()
    for phase in ("compile", "stage", "eval"):
        assert phase in s and s[phase]["seconds"] > 0
    assert s["compile"]["calls"] == 1
    assert s["scan_dispatch"]["calls"] == 1      # the second 2-chunk


def test_engine_books_host_path_spans(small_world):
    """The round's host path: one wait for staging and one host-to-device
    copy per chunk, the gather inside staging, and the staging thread's
    CPU seconds."""
    model, clients, test = small_world
    sim = FederatedSimulation(model, _fl(), clients, test)
    sim.run(rounds=4, eval_every=2)
    s = sim.timer.summary()
    for phase in ("stage_wait", "h2d", "stage_gather", "stage_cpu"):
        assert phase in s and s[phase]["seconds"] >= 0
    assert s["stage_wait"]["calls"] == 2 and s["h2d"]["calls"] == 2
    assert s["stage_gather"]["calls"] == s["stage"]["calls"] == 2
    assert s["stage_gather"]["seconds"] <= s["stage"]["seconds"]


def test_slow_staging_shows_as_wait_not_cpu(small_world, monkeypatch):
    """Staging that sleeps: the first chunk waits its whole staging, and
    the sleep is wall time of "stage" but not CPU time."""
    import time

    from repro.exec import engine
    stage = engine.stage_chunk

    def slow(*args, **kwargs):
        time.sleep(0.3)
        return stage(*args, **kwargs)
    monkeypatch.setattr(engine, "stage_chunk", slow)
    model, clients, test = small_world
    sim = FederatedSimulation(model, _fl(), clients, test)
    sim.run(rounds=2, eval_every=2)
    sec = sim.timer.seconds
    assert sec["stage_wait"] >= 0.2
    assert sec["stage"] >= 0.3
    assert sec["stage_cpu"] < sec["stage"]


def test_phase_opens_its_profiler_region(small_world, monkeypatch):
    """A phase opens a region named ``region`` (default: the phase), and
    the engine still opens the regions the benchmark's trace names."""
    import contextlib
    import threading

    from repro.obs import timing
    opened, lock = [], threading.Lock()

    def record(name):
        with lock:
            opened.append(name)
        return contextlib.nullcontext()
    monkeypatch.setattr(timing, "annotate", record)
    pt = PhaseTimes()
    with pt.phase("compile", region="train_chunk_n3"):
        pass
    with pt.phase("eval"):
        pass
    assert opened == ["train_chunk_n3", "eval"]
    model, clients, test = small_world
    sim = FederatedSimulation(model, _fl(), clients, test)
    sim.run(rounds=2, eval_every=2)
    for prefix in ("train_chunk_n", "stage_t", "eval", "stage_wait", "h2d",
                   "stage_gather"):
        assert any(n.startswith(prefix) for n in opened[2:]), prefix


def test_round_step_hlo_names_its_planes():
    """The compiled masked-plane train loop (with a comm plane) carries
    the client, comm and server planes in its ops' ``op_name``."""
    import re

    from repro.analysis import jaxpr_rules as jr
    h = jr.TraceHarness(jr._tiny_fl(comm_plane="q8"))
    assert h.fl.client_plane == "masked"
    txt = h.train_loop().lower(*h.loop_args()).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', txt)
    for plane in ("client_plane", "comm_plane", "server_plane"):
        assert any(f"/{plane}/" in n for n in names), plane


def test_provenance_block_and_diff():
    p = provenance()
    for k in COMPARE_KEYS + ("platform", "generated_unix"):
        assert k in p
    assert p["jax_version"] == jax.__version__
    assert diff(p, dict(p)) == []
    other = dict(p, backend="tpu", device_count=8)
    d = diff(p, other)
    assert any(x.startswith("backend:") for x in d)
    assert diff(None, p) == [] and diff(p, None) == []
