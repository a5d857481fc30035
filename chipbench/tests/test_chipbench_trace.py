"""The trace reduction and the trace readers on a hand-built trace with
known numbers, and on a trace recorded on a TPU v5e: a slice of 176.5
ms around the end of one round of ``cnn-t1-ama-fes``, reduced by
``Trace.to_json`` (``v5e_round_boundary.json``)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run, trace  # noqa: E402

MS = 1_000_000


def _hand_trace():
    # window 0..100 ms; device 0 runs the round program 10..60 (ops
    # 10-30, kernel 30-35, 40-60) and an eval program 70..80
    ops = trace.Events(["fusion.1", "custom-call.2", "fusion.3", "eval.1"],
                       [10 * MS, 30 * MS, 40 * MS, 70 * MS],
                       [30 * MS, 35 * MS, 60 * MS, 80 * MS])
    mods = trace.Events(["jit_train_loop(1)", "jit_eval_all(2)"],
                        [10 * MS, 70 * MS], [60 * MS, 80 * MS])
    host = trace.Events(["chipbench_window", "train_chunk_n1", "stage_t12",
                         "evaluator"],
                        [0, 5 * MS, 61 * MS, 68 * MS],
                        [100 * MS, 61 * MS, 69 * MS, 81 * MS])
    return trace.Trace({0: {"ops": ops, "modules": mods}}, host)


HAND_KERNEL = ('  custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %p), '
               'custom_call_target="tpu_custom_call"')
#: the server mix's line in the compiled round program of the recording
V5E_KERNEL = ('  %server_mix_flat.2 = f32[428,128]{1,0:T(8,128)S(1)} '
              'custom-call(%pad_maximum_fusion, %bitcast.133, %reshape.162), '
              'custom_call_target="tpu_custom_call"')


class _Rec(run.Record):
    def __init__(self, tr, rounds=2, program=HAND_KERNEL):
        win = {"start": 0.0, "done": [0.05 * (i + 1) for i in range(rounds)],
               "flops": 0.0, "phases": {}, "compiles": 0}
        super().__init__(None, win, 1.0, tr, [object()],
                         {"bf16_flops_per_s": 197e12}, {"round": program})


def _recorded():
    with open(Path(__file__).resolve().parent
              / "v5e_round_boundary.json") as f:
        return trace.Trace.from_json(json.load(f))


def _read(name, rec):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read(rec)


def test_busy_idle_and_gaps():
    tr = _hand_trace()
    assert tr.window == (0, 100 * MS)
    assert tr.busy_ns(0) == 55 * MS
    rec = _Rec(tr)
    assert _read("device_idle_share", rec) == pytest.approx(45.0)
    gaps = tr.idle_gaps(3)
    assert gaps[0] == ["no program span", 0.02]        # 80..100
    assert gaps[1] == ["train_chunk_n*", pytest.approx(0.01)]   # 0..10
    assert gaps[2][1] == pytest.approx(0.01)
    assert tr.top_ops(1) == [["fusion.1", 0.02]]


def test_plane_readers():
    rec = _Rec(_hand_trace(), rounds=2)
    assert _read("server_plane_ms_per_round", rec) == pytest.approx(2.5)
    # round program ops 10-30 and 40-60 ms, kernel left out
    assert _read("client_plane_ms_per_round", rec) == pytest.approx(20.0)


def test_kernel_missing_from_trace_is_an_error():
    tr = _hand_trace()
    ops = tr.devices[0]["ops"]
    tr.devices[0]["ops"] = ops.select(lambda n: n != "custom-call.2")
    with pytest.raises(LookupError, match="custom-call.2"):
        _read("server_plane_ms_per_round", _Rec(tr))


def test_an_op_around_others_is_not_counted_twice():
    tr = _hand_trace()
    ops = tr.devices[0]["ops"]
    # a loop op around the round's first ops, as a scan's while runs
    tr.devices[0]["ops"] = trace.Events(
        ops.names + ["while.9"], list(ops.start) + [10 * MS],
        list(ops.end) + [36 * MS])
    assert tr.busy_ns(0) == 55 * MS
    assert tr.top_ops(1) == [["fusion.1", 0.02]]
    rec = _Rec(tr, rounds=2)
    assert _read("client_plane_ms_per_round", rec) == pytest.approx(20.0)


def test_op_names_from_hlo_instructions():
    assert trace.op_name("%fusion.173 = f32[10,5]{1,0} fusion(f32[10,5]{1,0}"
                         " %get-tuple-element.424), kind=kLoop") == \
        "fusion.173"
    assert trace.op_name("jit_train_loop(17384)") == "jit_train_loop(17384)"


def test_recorded_v5e_trace():
    # numbers worked out by hand from the recording's 655 operations: a
    # scan's while (while.37) and the eval's loop (while) hold the
    # others; the round program's leaves take 5,931,811 ns, of which the
    # server mix 657 ns
    tr = _recorded()
    assert tr.busy_ns(0) == 6_599_425
    assert tr.top_ops(1) == [["reduce_window_max.24", 0.001785289]]
    assert tr.idle_gaps(1) == [["stage_t*", 0.166590536]]
    rec = _Rec(tr, rounds=1, program=V5E_KERNEL)
    assert _read("server_plane_ms_per_round", rec) == pytest.approx(657e-6)
    assert _read("client_plane_ms_per_round", rec) == \
        pytest.approx(5.931154)
    assert _read("device_idle_share", rec) == \
        pytest.approx(100 * (1 - 6_599_425 / 176_500_000))


def test_json_round_trip():
    tr = _hand_trace()
    back = trace.Trace.from_json(tr.to_json())
    assert back.window == tr.window
    assert back.busy_ns(0) == tr.busy_ns(0)
    assert back.host.names == tr.host.names
