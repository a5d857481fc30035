"""Readings that the limits of a cell's comparison are set from.

    python3 chipbench/control.py --workload <name> --seeds 11 12 13 \
        [--control 3] --out readings.json

For each seed, in one process on the cell's chips: set-up as a
benchmark run makes it (the program's first rounds), then the compared
numbers of the program against the f32 reference (the lower readings),
and for the first ``--control`` seeds those of the control (the
reference in the next lower precision, in the program's place) and of
each fault that ``Session.faults`` plants (the upper readings), with the
per-leaf gaps of the first update for a look by hand. Prints one JSON
object per seed and writes them all to ``--out``. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from chipbench import run  # noqa: E402


def readings(ctx, with_control: bool) -> dict:
    driver = run.load_module(run.HERE / "drivers"
                             / f"{ctx.traffic['driver']}.py")
    session = driver.Session(ctx)
    session.setup()
    session.release()
    gc.collect()
    out = {"seed": ctx.seed, "program": session.numbers(),
           "leaves": {"program": session.leaves()}}
    if with_control:
        out["control"] = session.control()
        runs = session.faults()
        out["faults"] = {f: session.numbers(g) for f, g in runs.items()}
        out["leaves"].update({f: session.leaves(g) for f, g in runs.items()})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) that also read the "
                         "control and the faults")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    ctx0 = run.Context(bench, args.workload, args.seeds[0])
    run.use_checkout_cache()
    run.devices_for(ctx0.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        row = readings(run.Context(bench, args.workload, seed),
                       i < args.control)
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
