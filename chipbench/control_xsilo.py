"""Readings that the cross-silo cell's limits are set from, at the
cell's size, written one row at a time as they come.

    python3 chipbench/control_xsilo.py --workload minitron4l-xsilo-4chip \
        --seeds 11 12 13 [--control 0] [--chips 1] --out readings.jsonl

For each seed, in one process: set-up as a benchmark run makes it (the
program's first rounds), then the compared numbers of the program
against the f32 reference (a lower reading); for the first
``--control`` seeds, those of the control (the reference in bfloat16 in
the program's place) and of each fault the reference plants
(``reference/xsilo.py`` ``FAULTS``), one at a time, so that the host
never holds more than one run beside the reference (upper readings).

With ``--chips`` below the cell's, the program (which needs the cell's
chips) is not run: only the upper readings are taken, the reference
training one silo a chip at a time, which reads what the cell's chips
would. Each reading is printed and appended to ``--out`` (JSON lines)
as it comes, so a run cut short keeps what it measured. The
benchmark's own runs never run this.
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


def readings(ctx, with_control: bool, program: bool = True):
    """Yields one row a reading: {"seed", "run", "numbers", "seconds"},
    ``run`` "program", "control" or the fault's name."""
    import jax.numpy as jnp
    from chipbench.reference import xsilo
    driver = run.load_module(run.HERE / "drivers"
                             / f"{ctx.traffic['driver']}.py")
    session = driver.Session(ctx)
    t = time.perf_counter()
    if program:
        session.setup()
        session.release()
        gc.collect()
        yield {"seed": ctx.seed, "run": "program",
               "numbers": session.numbers(),
               "seconds": time.perf_counter() - t}
    else:
        session.group = len(session.devices)
        session.make_weights()
    if not with_control:
        return
    for name in ("control",) + xsilo.FAULTS:
        t = time.perf_counter()
        got = (session.reference(jnp.bfloat16) if name == "control"
               else session.reference(fault=name))
        row = {"seed": ctx.seed, "run": name,
               "numbers": session.numbers(got)}
        del got
        gc.collect()
        yield {**row, "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=1,
                    help="seeds (the first ones) that also read the "
                         "control and the faults")
    ap.add_argument("--chips", type=int, default=None,
                    help="chips to use (the cell's by default); fewer "
                         "reads the control and the faults alone")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell_chips = run.Context(bench, args.workload, args.seeds[0]).chips
    chips = args.chips or cell_chips
    run.use_checkout_cache()
    run.devices_for(chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for i, seed in enumerate(args.seeds):
        ctx = run.Context(bench, args.workload, seed)
        ctx.chips = chips
        for row in readings(ctx, i < args.control, chips == cell_chips):
            print(json.dumps(row), flush=True)
            with open(out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
