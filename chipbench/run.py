"""The chip benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run is one process on the cell's chips. It finds everything by name
from ``BENCHMARK.json``: the cell's configuration
(``chipbench/configs/<config>.json``), its traffic
(``chipbench/traffic/<traffic>.json``), the traffic's driver
(``chipbench/drivers/<driver>.py``), the limits of its comparison
(``chipbench/limits/<workload>.json``) and one reader per metric
(``chipbench/metrics/<metric>.py``). It loads and warms up (set-up),
measures for ``--seconds``, checks the first rounds against the plain
reference, and prints one JSON object as the last line of standard
output. Everything printed before it is set-up information. With
``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics; with ``--trace 0`` the end-to-end ones.

It exits non-zero, printing no result, without a TPU or with fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the compile event jax.monitoring records for every program it loads,
#: compiled or read from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; have "
                   f"{[e['name'] for e in entries]}")


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: end-to-end ones with ``--trace 0``,
    per-layer ones with ``--trace 1``. A metric with a ``workloads`` key
    applies to those cells; a per-layer one without it, to every cell
    that reports the end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


class Context:
    """What a driver gets: the cell's files, the seed, and the run's
    set-up log, compile counter and trace spans."""

    def __init__(self, bench: dict, workload: str, seed: int,
                 traffic: dict | None = None):
        self.bench = bench
        self.cell = find(bench["workloads"], workload, "workload")
        cfg = find(bench["configs"], self.cell["config"], "config")
        self.config = load_json(ROOT / cfg["file"])
        # ``traffic`` stands in for the cell's file in the CPU tests
        self.traffic = traffic or load_json(
            HERE / "traffic" / f"{self.cell['traffic']}.json")
        self.limits = load_json(HERE / "limits" / f"{workload}.json")
        self.seed = seed
        self.chips = int(self.cell["chips"])
        self._compiles = 0

    def log(self, msg: str) -> None:
        print(f"setup: {time.perf_counter() - T0:.1f} s: {msg}", flush=True)

    def compiles(self) -> int:
        return self._compiles

    def count_compile(self, event: str, seconds: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self._compiles += 1

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield


def use_checkout_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names, so that only a cell's
    first run in a checkout compiles and two checkouts share nothing.
    Called before JAX is imported, which reads the variable then."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def max_rss_gib() -> float:
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def devices_for(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def checks(numbers: dict, limits: dict) -> list[tuple[str, float, float]]:
    return [(k, float(numbers[k]), float(limits[k])) for k in limits]


def measure(ctx: Context, seconds: float, trace: bool, devices,
            peaks: dict, t0: float = T0) -> dict:
    """Set-up, window, reference; returns the result line's fields."""
    import jax
    import numpy as np

    jax.monitoring.register_event_duration_secs_listener(ctx.count_compile)
    driver = load_module(HERE / "drivers" / f"{ctx.traffic['driver']}.py")
    session = driver.Session(ctx)
    session.setup()
    setup_s = time.perf_counter() - t0
    ctx.log(f"done; host memory peak {max_rss_gib():.2f} GiB")
    compiles0 = ctx.compiles()
    tr = None
    if trace:
        with tempfile.TemporaryDirectory(prefix="chipbench_trace") as tdir:
            from chipbench import trace as trace_mod
            with trace_mod.capture(tdir):
                win = session.window(seconds)
            tr = trace_mod.load(tdir)
    else:
        win = session.window(seconds)
    win["compiles"] = ctx.compiles() - compiles0
    if win["done"]:
        gaps = np.diff([win["start"]] + win["done"]) * 1e3
        print(f"window: {len(gaps)} rounds in "
              f"{win['done'][-1] - win['start']:.3f} s; round intervals ms "
              f"p10 {np.percentile(gaps, 10):.1f} p50 {np.median(gaps):.1f} "
              f"p90 {np.percentile(gaps, 90):.1f} max {gaps.max():.1f}",
              flush=True)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    programs = session.programs() if trace else {}
    session.release()
    gc.collect()
    numbers = session.numbers()
    record = Record(ctx, win, setup_s, tr, devices, peaks, programs)
    metrics = {}
    for m in cell_metrics(ctx.bench, ctx.cell["name"], trace):
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    cks = checks(numbers, ctx.limits)
    n_done = len(win["done"])
    out = {"correct": all(v <= lim for _, v, lim in cks),
           "attempted": win["attempted"],
           "failed": win["failed"] + (win["attempted"] - n_done),
           "metrics": metrics,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": peak}}
    if tr is not None:
        out["device"]["busy_s"] = record.busy_s()
        out["device"]["window_s"] = tr.window_ns() / 1e9
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in cks}
    return out


class Record:
    """What the metric readers read (``chipbench/metrics/<name>.py``)."""

    def __init__(self, ctx, win, setup_s, trace, devices, peaks, programs):
        self.ctx, self.win, self.setup_s = ctx, win, setup_s
        self.trace, self.devices, self.peaks = trace, devices, peaks
        #: {name: compiled HLO text} of the programs the window drove
        self.programs = programs
        self.chips = len(devices)

    @property
    def rounds(self) -> int:
        return len(self.win["done"])

    @property
    def window_s(self) -> float:
        """From the window's start to the last round completed."""
        return self.win["done"][-1] - self.win["start"]

    def busy_s(self) -> float:
        tr = self.trace
        return sum(tr.busy_ns(d) for d in tr.devices) / 1e9 / len(
            tr.devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    bench = load_json(ROOT / "BENCHMARK.json")
    ctx = Context(bench, args.workload, args.seed)
    use_checkout_cache()
    try:
        devices = devices_for(ctx.chips)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    peaks = peaks_for(devices[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    ctx.log(f"compile cache: {enable_compile_cache()}")
    import jax
    # keep the quick programs too, so a warm set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = measure(ctx, args.seconds, bool(args.trace), devices, peaks)
    print(f"host memory peak {max_rss_gib():.2f} GiB", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
