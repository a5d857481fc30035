"""Reduce a profiler trace to what the per-layer readers need.

``capture(dir)`` wraps the window in ``jax.profiler`` tracing; ``load``
reads the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData`` and
keeps, per device plane (``/device:TPU:<n>``), the operation events
(line ``XLA Ops``) and the program events (line ``XLA Modules``) as
``(name, start_ns, end_ns)`` arrays, and from the host planes the named
spans: the program's ``annotate`` regions (``train_chunk_n*``,
``stage_t*``, ``eval``, ``evaluator``) and the benchmark's own
(``chipbench_*``). ``Trace.to_json``/``from_json`` turn a reduced trace
into plain JSON and back, the form a recorded trace is kept in for the
readers' tests.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPAN = re.compile(r"^(train_chunk_n\d+|stage_t\d+|eval|evaluator|"
                       r"chipbench_\w+)$")
WINDOW_SPAN = "chipbench_window"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")
#: a TPU trace names an operation event by its whole HLO instruction,
#: ``%fusion.3 = f32[...] fusion(...), ...``; the readers use its name
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def op_name(event_name: str) -> str:
    m = INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


@contextlib.contextmanager
def capture(path: str):
    """Trace the device and the host's named spans. The Python tracer,
    on by default, is off: it records every Python call of the window,
    which slows the host it measures and makes the trace too large to
    read back in a run's time."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class Events:
    """Events of one kind on one timeline, sorted by start, and of those
    that start together the longest first."""

    def __init__(self, names, start, end):
        start = np.asarray(start, np.int64).reshape(-1)
        end = np.asarray(end, np.int64).reshape(-1)
        order = np.lexsort((-end, start))
        self.names = [names[i] for i in order]
        self.start = start[order]
        self.end = end[order]

    def __len__(self):
        return len(self.names)

    def clip(self, lo: int, hi: int) -> "Events":
        keep = (self.end > lo) & (self.start < hi)
        idx = np.flatnonzero(keep)
        return Events([self.names[i] for i in idx],
                      np.maximum(self.start[idx], lo),
                      np.minimum(self.end[idx], hi))

    def select(self, pred) -> "Events":
        idx = [i for i, n in enumerate(self.names) if pred(n)]
        return Events([self.names[i] for i in idx], self.start[idx],
                      self.end[idx])

    def leaves(self) -> "Events":
        """The events that hold no other: an event on one timeline that
        the next one starts inside (a loop around its body's ops) is left
        out, so that durations add up without counting a body twice."""
        if len(self) < 2:
            return self
        outer = np.append(self.start[1:] < self.end[:-1], False)
        idx = np.flatnonzero(~outer)
        return Events([self.names[i] for i in idx], self.start[idx],
                      self.end[idx])

    def total_ns(self) -> int:
        return int(np.sum(self.end - self.start))

    def to_json(self):
        return [self.names, self.start.tolist(), self.end.tolist()]

    @classmethod
    def from_json(cls, x):
        return cls(*x)


def union(ev: Events) -> list[tuple[int, int]]:
    """Merged busy intervals of ``ev``."""
    out = []
    for s, e in zip(ev.start.tolist(), ev.end.tolist()):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def covered_ns(intervals, lo: int, hi: int) -> int:
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in intervals)


class Trace:
    def __init__(self, devices: dict, host: Events, window=None):
        self.devices = devices          # {index: {"ops": Events, "modules": Events}}
        self.host = host
        if window is None:
            w = host.select(lambda n: n == WINDOW_SPAN)
            window = ((int(w.start[0]), int(w.end[-1])) if len(w)
                      else None)
        self.window = window

    def to_json(self) -> dict:
        return {"window": list(self.window) if self.window else None,
                "host": self.host.to_json(),
                "devices": {str(k): {n: v.to_json() for n, v in d.items()}
                            for k, d in self.devices.items()}}

    @classmethod
    def from_json(cls, x: dict) -> "Trace":
        devs = {int(k): {n: Events.from_json(v) for n, v in d.items()}
                for k, d in x["devices"].items()}
        return cls(devs, Events.from_json(x["host"]),
                   tuple(x["window"]) if x["window"] else None)

    # -- reductions -----------------------------------------------------
    def window_ops(self, dev: int) -> Events:
        """The device's operations in the window, leaves only."""
        lo, hi = self.window
        return self.devices[dev]["ops"].clip(lo, hi).leaves()

    def busy_ns(self, dev: int) -> int:
        lo, hi = self.window
        return covered_ns(union(self.window_ops(dev)), lo, hi)

    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def in_modules(self, dev: int, pattern: str) -> Events:
        """Window ops that run inside a program whose name matches."""
        ops = self.window_ops(dev)
        mods = self.devices[dev]["modules"].select(
            lambda n: re.search(pattern, n) is not None)
        spans = union(mods)
        if not spans:
            return ops.select(lambda n: False)
        s = np.array([a for a, _ in spans])
        e = np.array([b for _, b in spans])
        k = np.searchsorted(s, ops.start, side="right") - 1
        inside = (k >= 0) & (ops.start < e[np.maximum(k, 0)])
        idx = np.flatnonzero(inside)
        return Events([ops.names[i] for i in idx], ops.start[idx],
                      ops.end[idx])

    def top_ops(self, n: int = 10) -> list:
        tot: dict[str, int] = {}
        for d in self.devices:
            ops = self.window_ops(d)
            for name, dur in zip(ops.names, (ops.end - ops.start).tolist()):
                tot[name] = tot.get(name, 0) + dur
        k = len(self.devices)
        return [[name, ns / 1e9 / k] for name, ns in
                sorted(tot.items(), key=lambda x: -x[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of device 0, each named by the host span
        that was open at its middle (digits folded to ``*``); a gap in
        no span of the program is the benchmark's own host time."""
        lo, hi = self.window
        busy = union(self.window_ops(min(self.devices)))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            inner = [(hs, nm) for nm, hs, he in zip(
                self.host.names, self.host.start.tolist(),
                self.host.end.tolist())
                if hs <= mid < he and nm != WINDOW_SPAN]
            name = max(inner)[1] if inner else "no program span"
            out.append([re.sub(r"\d+", "*", name), (e - s) / 1e9])
        return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {path}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, host = {}, ([], [], [])
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            d = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    names, s, e = [], [], []
                    for ev in line.events:
                        names.append(op_name(ev.name))
                        s.append(int(ev.start_ns))
                        e.append(int(ev.start_ns + ev.duration_ns))
                    d["ops" if line.name == OPS_LINE else "modules"] = \
                        Events(names, s, e)
            d.setdefault("ops", Events([], [], []))
            d.setdefault("modules", Events([], [], []))
            devices[int(m.group(1))] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPAN.match(ev.name):
                        host[0].append(ev.name)
                        host[1].append(int(ev.start_ns))
                        host[2].append(int(ev.start_ns + ev.duration_ns))
    return Trace(devices, Events(*host))

