"""The cross-silo cell's own files: the token generator, the required
FLOPs, the ``client_reduce`` readers on hand-made traces, and whole runs
of the cell at a CPU size: a sound run is correct, the control and
every planted fault are not, and on four devices the round reduces
over the client axis under the ``client_reduce`` scope."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import xsilo_tiny  # noqa: E402

from chipbench import control, control_xsilo, flops_lm, run, trace  # noqa: E402
from chipbench.gen import tokens  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000


# ------------------------------------------------------------- tokens
def test_tokens_repeat_per_seed_round_and_silo():
    big = 2**31 + 12345
    a = tokens.round_tokens(big, 3, [2, 0], (2, 2, 64), 32000, 1.0)
    b = tokens.round_tokens(big, 3, [2, 0], (2, 2, 64), 32000, 1.0)
    assert a.shape == (2, 2, 2, 64) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        a[0], tokens.silo_tokens(big, 3, 2, (2, 2, 64), 32000, 1.0))
    assert not np.array_equal(
        a, tokens.round_tokens(big, 4, [2, 0], (2, 2, 64), 32000, 1.0))
    assert not np.array_equal(
        a, tokens.round_tokens(big + 1, 3, [2, 0], (2, 2, 64), 32000, 1.0))


def test_tokens_stay_in_the_slice_and_follow_zipf():
    x = tokens.silo_tokens(7, 0, 1, (400_000,), 32000, 1.0)
    assert x.min() >= 0 and x.max() < 32000
    counts = np.bincount(x, minlength=32000)
    ranked = np.sort(counts)[::-1][:100]
    slope = np.polyfit(np.log(np.arange(1, 101)), np.log(ranked), 1)[0]
    assert -1.15 < slope < -0.85, slope


def test_silos_differ_in_their_frequent_ids():
    top = [set(np.argsort(np.bincount(
        tokens.silo_tokens(7, 0, s, (100_000,), 32000, 1.0),
        minlength=32000))[-20:]) for s in range(4)]
    for i in range(4):
        for j in range(i):
            assert len(top[i] & top[j]) < 5


# -------------------------------------------------------------- flops
def test_lm_flops_against_a_hand_count():
    c = {"d_model": 8, "num_heads": 3, "num_kv_heads": 1, "head_dim": 4,
         "d_ff": 16, "vocab_size": 10, "num_layers": 3, "fes_tail_layers": 1,
         "mlp_act": "relu2"}
    # per layer: q 8x12, k 8x4, v 8x4, o 12x8, mlp 2 x 8x16
    assert flops_lm.layer_matmul_params(c) == 96 + 32 + 32 + 96 + 256
    S = 5
    layer = 2 * S * 512 + 2 * S * S * 3 * 4
    head = 2 * S * 8 * 10
    assert flops_lm.forward(c, S) == (2 * layer, layer + head)
    f = 3 * layer + head
    assert flops_lm.round_flops(c, 2, 3, S, [False, True]) == \
        2 * 3 * ((f + 2 * f) + (f + 2 * (layer + head)))


def test_minitron_cell_round_flops_match_the_issue_estimate():
    cfg = run.load_json(run.HERE / "configs" / "minitron-8b-4l.json")
    full = flops_lm.round_flops(cfg, 4, 2, 2048, [False])
    # 6 x 902.9 M matmul weights x 16,384 tokens, plus causal attention
    assert flops_lm.layer_matmul_params(cfg) * 4 + 4096 * 32000 == \
        902_823_936
    assert full == pytest.approx(9.37e13, rel=0.01)


# ------------------------------------------------------------ readers
PROGRAM = "\n".join([
    '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
    '{op_name="jit(train_loop)/while/body/client_plane/dot_general"}',
    '  %all-gather.2 = f32[8]{0} all-gather(%q), metadata={op_name='
    '"jit(train_loop)/while/body/server_plane/client_reduce/'
    'sharding_constraint"}',
    '  %fusion.3 = f32[2]{0} fusion(%r), kind=kCustom, metadata={op_name='
    '"jit(train_loop)/while/body/server_plane/client_reduce/c...,c->.../'
    'dot_general" stack_frame_id=4}',
])


def _trace(reduce_spans, other_spans):
    """Two chips, a 100 ms window, one round program 0..100 ms."""
    devs = {}
    for d in (0, 1):
        names, s, e = [], [], []
        for n, (a, b) in [("fusion.1", x) for x in other_spans] + list(
                zip(["all-gather.2", "fusion.3"], reduce_spans)):
            names.append(n)
            s.append(a * MS)
            e.append(b * MS)
        devs[d] = {"ops": trace.Events(names, s, e),
                   "modules": trace.Events(["jit_train_loop(7)"], [0],
                                           [100 * MS])}
    host = trace.Events(["chipbench_window"], [0], [100 * MS])
    return trace.Trace(devs, host)


class _Rec(run.Record):
    def __init__(self, tr, program=PROGRAM, rounds=2):
        win = {"start": 0.0, "done": [0.05, 0.1], "flops": 0.0,
               "phases": {}, "compiles": 0}
        super().__init__(None, win, 1.0, tr, [object(), object()],
                         {"bf16_flops_per_s": 197e12}, {"round": program})


def _read(name, rec):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read(rec)


def test_xsilo_round_mfu_reads_as_round_mfu():
    rec = _Rec(None)
    rec.win["flops"] = 3.5e14
    want = 100.0 * 3.5e14 / (rec.window_s * 2 * 197e12)
    assert _read("xsilo_round_mfu", rec) == _read("round_mfu", rec) == want


def test_reduce_ops_found_by_scope():
    mod = run.load_module(run.HERE / "metrics"
                          / "client_reduce_ms_per_round.py")
    assert mod.reduce_ops(_Rec(None)) == {"all-gather.2", "fusion.3"}


def test_fully_exposed_reduction():
    # reduction 60-70 and 80-90 ms, other work 0-50 ms: nothing hides it
    rec = _Rec(_trace([(60, 70), (80, 90)], [(0, 50)]))
    assert _read("client_reduce_ms_per_round", rec) == pytest.approx(10.0)
    assert _read("client_reduce_exposed_share", rec) == pytest.approx(100.0)


def test_fully_overlapped_reduction():
    # reduction 10-20 and 30-40 ms inside other work 0-50 ms
    rec = _Rec(_trace([(10, 20), (30, 40)], [(0, 50)]))
    assert _read("client_reduce_ms_per_round", rec) == pytest.approx(10.0)
    assert _read("client_reduce_exposed_share", rec) == pytest.approx(0.0)


def test_half_hidden_reduction():
    rec = _Rec(_trace([(40, 60), (90, 95)], [(0, 50)]))
    assert _read("client_reduce_exposed_share", rec) == \
        pytest.approx(100 * 15 / 25)


def test_readers_are_silent_without_the_scope():
    tr = _trace([(60, 70), (80, 90)], [(0, 50)])
    plain = PROGRAM.replace("client_reduce/", "")
    for name in ("client_reduce_ms_per_round", "client_reduce_exposed_share"):
        assert _read(name, _Rec(tr, program=plain)) is None
        assert _read(name, _Rec(None)) is None


# -------------------------------------------------------- whole runs
def _fails(numbers: dict, limits: dict) -> list:
    return [k for k in limits if not numbers[k] <= limits[k]]


def test_sound_run_is_correct_and_control_and_faults_are_not():
    ctx = xsilo_tiny.xsilo_context(11, seq=32)
    r = control.readings(ctx, True)
    assert not _fails(r["program"], ctx.limits), r["program"]
    assert _fails(r["control"], ctx.limits), r["control"]
    for fault, numbers in r["faults"].items():
        assert _fails(numbers, ctx.limits), (fault, numbers)


def test_reference_one_silo_at_a_time_reads_as_all_at_once():
    """The reference training one silo at a time (one chip a silo's f32
    step) differs from the stacked one only by the order of the f32 sum
    of the silos' updates: far under every limit."""
    from chipbench.reference import xsilo
    ctx = xsilo_tiny.xsilo_context(7, seq=32)
    s = run.load_module(run.HERE / "drivers" / "cohorts.py").Session(ctx)
    s.make_weights()
    stacked = s.reference()
    s.group = 1
    one = s.reference()
    np.testing.assert_allclose(one["loss"], stacked["loss"], rtol=1e-6)
    gaps = s.numbers(one)
    assert all(v <= lim / 100 for v, lim in
               ((gaps[k], ctx.limits[k]) for k in ctx.limits)), gaps
    with pytest.raises(ValueError):
        s.group = 3
        s.reference(fault=xsilo.FAULTS[0])


def test_readings_without_the_program_fail_every_fault(tmp_path):
    """On fewer chips than the cell's the script reads the control and
    each planted fault alone, one silo at a time; each fails a limit."""
    from chipbench.reference import xsilo
    ctx = xsilo_tiny.xsilo_context(12, seq=32)
    rows = list(control_xsilo.readings(ctx, True, program=False))
    assert [r["run"] for r in rows] == ["control", *xsilo.FAULTS]
    for r in rows:
        assert _fails(r["numbers"], ctx.limits), r


FOUR = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{tests!r}, {root!r}, {src!r}]
    import jax
    import xsilo_tiny
    from chipbench import run
    ctx = xsilo_tiny.xsilo_context(13, seq=32)
    s = run.load_module(run.HERE / "drivers" / "cohorts.py").Session(ctx)
    s.setup()
    rec = type("R", (), {{"programs": s.programs()}})
    crm = run.load_module(run.HERE / "metrics"
                          / "client_reduce_ms_per_round.py")
    names = sorted(crm.reduce_ops(rec))
    mesh = dict(s.pod.runner.mesh.shape)
    s.release()
    nums = s.numbers()
    print("RESULT " + json.dumps({{
        "correct": all(nums[k] <= v for k, v in ctx.limits.items()),
        "numbers": nums, "mesh": mesh, "reduce_ops": names}}))
""")


def test_four_devices_reduce_under_the_client_reduce_scope():
    tests = str(Path(__file__).resolve().parent)
    script = FOUR.format(tests=tests, root=str(ROOT), src=str(ROOT / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    out = json.loads(line[0][len("RESULT "):])
    assert out["correct"] is True, out["numbers"]
    assert out["mesh"]["client"] == 4
    # the gather of the server's state and the reduction of the
    # clients' sum both carry the scope
    ops = out["reduce_ops"]
    assert any(n.startswith("all-gather") for n in ops), ops
    assert any(n.startswith(("all-reduce", "reduce-scatter")) for n in ops), \
        ops


def test_hidden_time_against_a_pairwise_count():
    mod = run.load_module(run.HERE / "metrics"
                          / "client_reduce_exposed_share.py")
    rng = np.random.RandomState(0)
    for _ in range(20):
        a = trace.union(trace.Events(
            ["x"] * 30, *np.sort(rng.randint(0, 1000, (2, 30)), axis=0)))
        b = trace.union(trace.Events(
            ["y"] * 40, *np.sort(rng.randint(0, 1000, (2, 40)), axis=0)))
        want = sum(trace.covered_ns(b, s, e) for s, e in a)
        assert mod.hidden_ns(a, b) == want
