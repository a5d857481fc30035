"""A cell of the benchmark at a size a CPU test run can hold."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run  # noqa: E402

BENCH = run.load_json(ROOT / "BENCHMARK.json")


def paper_context(seed: int = 5) -> "run.Context":
    """``cnn-t1-ama-fes`` shrunk: 6 clients, 3 a round, one epoch."""
    tr = run.load_json(run.HERE / "traffic" / "t1-ama-fes.json")
    tr["generator"].update(n_train=1200, n_test=500)
    tr["fl"].update(num_clients=6, clients_per_round=3, local_epochs=2,
                    local_batch_size=16)
    return run.Context(BENCH, "cnn-t1-ama-fes", seed, traffic=tr)


def measure(ctx, seconds: float = 1.0) -> dict:
    """A whole run past the look for a chip, on the CPU."""
    import time

    import jax
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return run.measure(ctx, seconds, False, jax.devices()[:1], peaks,
                       t0=time.perf_counter())
