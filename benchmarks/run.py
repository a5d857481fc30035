"""Benchmark entrypoint: one function per paper table/figure + kernels.

``python -m benchmarks.run``          — quick mode (CI-sized)
``python -m benchmarks.run --smoke``  — tiny pass (the CI rot check:
                                        every sub-benchmark must run)
``python -m benchmarks.run --full``   — paper-scale miniatures (slower)

Every sub-benchmark routes through the current registries (server
strategies, environments) and the fused server-plane API — the engine
throughput sweep with a committed baseline is
``benchmarks/sim_engine.py`` (gated in CI by
``scripts/check_bench.py``). The roofline sweep
(40 pairs, heavy compiles) stays separate:
``python benchmarks/roofline.py``.
"""
from __future__ import annotations

import os
import sys

# runnable both as `python -m benchmarks.run` and `python benchmarks/run.py`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    full = "--full" in sys.argv
    smoke = "--smoke" in sys.argv and not full
    quick = not full
    print("name,value,derived")

    print("# --- Fig.2: sync AMA-FES vs naive FL vs FedProx ---")
    from benchmarks import fig2_sync
    if smoke:
        fig2_sync.run(rounds=2, n_train=240, num_clients=8, m=4, quick=True)
    else:
        fig2_sync.run(quick=quick)

    print("# --- Fig.3: async AMA delay tolerance ---")
    from benchmarks import fig3_async
    fig3_async.run(rounds=2 if smoke else 60, quick=quick)

    print("# --- kernels (incl. fused server plane) ---")
    from benchmarks import kernels_bench
    kernels_bench.run(quick=quick, smoke=smoke)

    print("# --- round engine: fused scan vs per-round jit ---")
    from benchmarks import round_scan
    round_scan.run(quick=quick, smoke=smoke)

    if full:
        print("# --- ablation: adaptive vs fixed alpha ---")
        from benchmarks import ablation_alpha
        ablation_alpha.run()

    print("# done. engine sweep: benchmarks/sim_engine.py; roofline: "
          "experiments/roofline.md (python benchmarks/roofline.py)")


if __name__ == "__main__":
    main()
