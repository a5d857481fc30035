"""client_reduce_ms_per_round: device time of the round program's
operations under the ``client_reduce`` scope (the cross-chip reduction
of the clients' weighted sum and the gather of the server's state,
``core/round.py`` and ``sharding/ctx.py``), the union of their intervals
on each chip averaged over the chips, per round. The operations are
found by the ``op_name`` metadata of the compiled round program's
instructions. Nothing to read where the program has no such scope."""
import re

import numpy as np

from chipbench.metrics import _programs as P
from chipbench.trace import Events, union

SCOPE = "client_reduce"
INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                         r'op_name="([^"]*)"', re.M)


def reduce_ops(r) -> set:
    """Names of the compiled round program's instructions whose op_name
    lies under the ``client_reduce`` scope."""
    text = (getattr(r, "programs", None) or {}).get("round", "")
    return {name for name, op in INSTRUCTION.findall(text)
            if SCOPE in op.split("/")}


def round_events(r, dev) -> Events:
    """The device's operations in the window inside the round program,
    containers included (a collective that other operations run inside
    is kept, not dropped as a loop)."""
    lo, hi = r.trace.window
    ops = r.trace.devices[dev]["ops"].clip(lo, hi)
    spans = union(r.trace.devices[dev]["modules"].select(
        lambda n: re.search(P.ROUND_MODULE, n) is not None))
    if not spans or not len(ops):
        return ops.select(lambda n: False)
    s = np.array([a for a, _ in spans])
    e = np.array([b for _, b in spans])
    k = np.searchsorted(s, ops.start, side="right") - 1
    idx = np.flatnonzero((k >= 0) & (ops.start < e[np.maximum(k, 0)]))
    return Events([ops.names[i] for i in idx], ops.start[idx], ops.end[idx])


def split(r, dev) -> tuple[list, list]:
    """(union of the reduction's intervals, union of the other leaf
    operations' intervals) on one device in the window."""
    names = reduce_ops(r)
    ev = round_events(r, dev)
    red = ev.select(lambda n: n in names)
    other = ev.select(lambda n: n not in names).leaves()
    return union(red), union(other)


def busy_ns(intervals) -> int:
    return sum(e - s for s, e in intervals)


def read(r):
    if r.trace is None or not r.rounds or not reduce_ops(r):
        return None
    tot = sum(busy_ns(split(r, d)[0]) for d in r.trace.devices)
    return tot / len(r.trace.devices) / 1e6 / r.rounds if tot else None
