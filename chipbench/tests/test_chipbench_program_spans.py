"""The readers of the program's host spans and counter: each is the
phase's seconds in the window, in ms a round, and ``None`` when the
program books no such phase."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run  # noqa: E402

#: metric -> the PhaseTimes key it reads
READS = {"stage_wait_ms_per_round": "stage_wait",
         "h2d_ms_per_round": "h2d",
         "stage_gather_ms_per_round": "stage_gather",
         "stage_cpu_ms_per_round": "stage_cpu"}

PHASES = {"stage": 1.2, "stage_wait": 0.3, "h2d": 0.06,
          "stage_gather": 1.1, "stage_cpu": 0.9, "eval": 0.01}


class _Rec(run.Record):
    def __init__(self, phases, rounds=4):
        win = {"start": 0.0, "done": [0.5 * (i + 1) for i in range(rounds)],
               "flops": 0.0, "phases": phases, "compiles": 0}
        super().__init__(None, win, 1.0, None, [object()], {}, {})


def _read(name, rec):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read(rec)


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_is_ms_per_round(name):
    assert _read(name, _Rec(PHASES)) == pytest.approx(
        PHASES[READS[name]] * 1e3 / 4)


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_is_none_when_the_phase_is_absent(name):
    phases = {k: v for k, v in PHASES.items() if k != READS[name]}
    assert _read(name, _Rec(phases)) is None
    assert _read(name, _Rec(PHASES, rounds=0)) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_metric_is_declared_for_the_cell(name):
    bench = run.load_json(ROOT / "BENCHMARK.json")
    m = run.find(bench["per_layer"], name, "metric")
    assert m["moves"] == "rounds_per_s" and m["unit"] == "ms"
    assert name in {x["name"] for x in
                    run.cell_metrics(bench, "cnn-t1-ama-fes", True)}
