"""The reader of the program's ``stage_device`` counter: 100 where the
window's chunks were gathered from a device store, and ``None`` where
the program books no such counter."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run  # noqa: E402

NAME = "stage_device_share"
PHASES = {"stage": 1.2, "stage_wait": 0.3, "h2d": 0.06,
          "stage_gather": 0.04, "stage_cpu": 0.9, "eval": 0.01}


class _Rec(run.Record):
    def __init__(self, phases, rounds=4):
        win = {"start": 0.0, "done": [0.5 * (i + 1) for i in range(rounds)],
               "flops": 0.0, "phases": phases, "compiles": 0}
        super().__init__(None, win, 1.0, None, [object()], {}, {})


def _read(rec):
    return run.load_module(run.HERE / "metrics" / f"{NAME}.py").read(rec)


@pytest.mark.parametrize("gather", [0.04, 1e-4])
def test_reader_is_the_device_share_of_the_gather(gather):
    """The counter books zero seconds; with it, every chunk of the
    window was gathered on the device, however long the gather took."""
    assert _read(_Rec({**PHASES, "stage_gather": gather,
                       "stage_device": 0.0})) == 100.0


def test_reader_is_none_without_the_counter():
    assert _read(_Rec(PHASES)) is None
    assert _read(_Rec({**PHASES, "stage_device": 0.0,
                       "stage_gather": 0.0})) is None


def test_metric_is_declared_for_the_cell():
    bench = run.load_json(ROOT / "BENCHMARK.json")
    m = run.find(bench["per_layer"], NAME, "metric")
    assert m["moves"] == "rounds_per_s" and m["unit"] == "%"
    assert m["source"] == "program_counter"
    assert NAME in {x["name"] for x in
                    run.cell_metrics(bench, "cnn-t1-ama-fes", True)}
