"""Scoped wall-clock phase timers + jax.profiler hooks.

JAX dispatch is asynchronous: a jitted call returns as soon as the work
is ENQUEUED, so ``time.time()`` around it measures dispatch latency,
not execution — the bug the seed launchers and several benchmarks had.
Every timer here is ``time.perf_counter`` (monotonic, immune to wall
clock steps) and closes its span with ``jax.block_until_ready`` on the
computation's outputs, so a phase's seconds are the seconds the device
actually spent.

``PhaseTimes`` accumulates named phases across a run; the execution
engine carries one and the ``MetricsLogger`` serializes its summary.
Every phase is also a ``TraceAnnotation`` region (``annotate``) opened
by the same call, so a ``--profile`` trace shows each phase on the
device trace's clock and the two cannot drift apart. The engine's
phases, by thread:

  main thread (``SimulationEngine.run`` / ``ChunkRunner``):
    ``stage_wait``    asking the staged iterator for the next chunk until
                      holding it; with ``prefetch=False`` it holds the
                      chunk's whole inline staging
    ``h2d``           the staged chunk's host-to-device copy, closed on
                      the device arrays before the dispatch; a chunk
                      gathered from a device store copies nothing, and
                      the span holds the wait for the gather's output
    ``compile`` / ``scan_dispatch`` / ``round_dispatch``
                      [region ``train_chunk_n<n>``] one train-loop
                      dispatch to its outputs; "compile" is the first
                      call per chunk shape (trace + XLA compile + the
                      first execution)
    ``eval``          the evaluation, to accuracy and loss on the host
    ``checkpoint``    saving the round state
  staging thread (``ChunkPrefetcher``; the main thread without prefetch):
    ``stage``         [region ``stage_t<t0>``] schedules, index draw and
                      gather of one chunk
    ``stage_gather``  the gather alone, inside ``stage``: the host
                      fancy-gather, or from a device store the index
                      upload and the gather's dispatch
    ``stage_cpu``     a counter, not a span: the staging thread's CPU
                      seconds inside ``stage`` (``cpu=True``)
    ``stage_device``  a counter, not a span: zero seconds, one call
                      for each chunk gathered from a device store

A phase's region has the phase's name unless given in brackets.
The evaluator adds its own region, ``evaluator``, inside ``eval``.
Phases of distinct names may overlap (staging runs beside dispatch);
a ``*_cpu`` key holds CPU seconds, not wall time.

``profile_trace`` is the ``--profile <dir>`` hook: a
``jax.profiler.trace`` context around the run, so the resulting
TensorBoard trace carries the engine's own phase structure.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

__all__ = ["PhaseTimes", "sync_time", "profile_trace", "annotate"]


def _block(tree) -> None:
    try:
        jax.block_until_ready(tree)
    except Exception:      # host-only values (floats, History, ...)
        pass


def sync_time(fn, *args, **kwargs):
    """(seconds, result) of ``fn(*args, **kwargs)`` with the span closed
    by ``block_until_ready`` on the result — the one true way to time a
    jitted call."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    _block(out)
    return time.perf_counter() - t0, out


class _Span:
    """Yielded by ``PhaseTimes.phase``; call ``sync(tree)`` with the
    device outputs whose completion closes the span."""

    __slots__ = ("_tree",)

    def __init__(self):
        self._tree = None

    def sync(self, tree):
        self._tree = tree
        return tree


class PhaseTimes:
    """Thread-safe accumulator of named wall-clock phases.

    The staging phase runs on the prefetcher's worker thread while scan
    dispatch runs on the main thread, so accumulation takes a lock;
    phase SPANS of distinct names may overlap (that is the point of
    prefetching — the summary records where time was spent, not a
    partition of the wall)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds: dict[str, float] = {}
        self.calls: dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self.seconds[name] = self.seconds.get(name, 0.0) + seconds
            self.calls[name] = self.calls.get(name, 0) + 1

    @contextlib.contextmanager
    def phase(self, name: str, region: str | None = None,
              cpu: bool = False):
        """``with times.phase("eval") as span: span.sync(out)`` — the
        span closes only after the synced outputs are ready. It runs
        inside the profiler region ``region`` (default: ``name``); with
        ``cpu=True`` it also books the calling thread's CPU seconds
        under ``f"{name}_cpu"``."""
        span = _Span()
        with annotate(region or name):
            c0 = time.thread_time() if cpu else 0.0
            t0 = time.perf_counter()
            try:
                yield span
            finally:
                if span._tree is not None:
                    _block(span._tree)
                self.add(name, time.perf_counter() - t0)
                if cpu:
                    self.add(f"{name}_cpu", time.thread_time() - c0)

    def summary(self) -> dict:
        """{phase: {"seconds": s, "calls": n}}, insertion-ordered."""
        with self._lock:
            return {k: {"seconds": round(self.seconds[k], 6),
                        "calls": self.calls[k]}
                    for k in self.seconds}

    def total(self) -> float:
        with self._lock:
            return sum(self.seconds.values())


def profile_trace(outdir: str | None):
    """``jax.profiler.trace`` context for ``--profile <dir>``; a no-op
    context when ``outdir`` is falsy (the flag's default)."""
    if not outdir:
        return contextlib.nullcontext()
    return jax.profiler.trace(outdir)


def annotate(name: str):
    """Named ``TraceAnnotation`` region (shows up in the profiler
    timeline); degrades to a no-op context if the profiler API is
    unavailable in this jax build."""
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()
