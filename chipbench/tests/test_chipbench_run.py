"""run.py refuses to measure without a TPU, and the metric selection
follows BENCHMARK.json."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run  # noqa: E402


def test_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "cnn-t1-ama-fes",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[-1] \
        .startswith("{")


def test_cell_metrics_follow_workload_keys():
    bench = {"end_to_end": [{"name": "a", "moves": None},
                            {"name": "b", "workloads": ["x"]}],
             "per_layer": [{"name": "p", "moves": "a"},
                           {"name": "q", "moves": "b"},
                           {"name": "r", "moves": "a", "workloads": ["y"]}]}
    assert [m["name"] for m in run.cell_metrics(bench, "x", False)] == \
        ["a", "b"]
    assert [m["name"] for m in run.cell_metrics(bench, "y", False)] == ["a"]
    assert [m["name"] for m in run.cell_metrics(bench, "x", True)] == \
        ["p", "q"]
    assert [m["name"] for m in run.cell_metrics(bench, "y", True)] == \
        ["p", "r"]
