"""client_reduce_exposed_share: of the device time of the round
program's ``client_reduce`` operations (``client_reduce_ms_per_round``),
the share during which no other operation of the same chip is busy,
summed over the chips, in percent: 0 where other work hides the
reduction entirely, 100 where the chip does nothing else meanwhile."""
import numpy as np

from chipbench import run

_crm = run.load_module(run.HERE / "metrics" / "client_reduce_ms_per_round.py")


def hidden_ns(red, other) -> int:
    """Time of the disjoint sorted intervals ``red`` that the disjoint
    sorted intervals ``other`` cover, through the running total of
    ``other``'s covered time (no loop over pairs)."""
    if not red or not other:
        return 0
    bs, be = (np.array(x, np.int64) for x in zip(*other))
    cum = np.concatenate([[0], np.cumsum(be - bs)])

    def covered_to(t):
        i = np.searchsorted(bs, t, side="right")
        j = np.maximum(i - 1, 0)
        part = np.clip(t - bs[j], 0, be[j] - bs[j])
        return np.where(i > 0, cum[j] + part, 0)

    rs, re_ = (np.array(x, np.int64) for x in zip(*red))
    return int(np.sum(covered_to(re_) - covered_to(rs)))


def read(r):
    if r.trace is None or not r.rounds or not _crm.reduce_ops(r):
        return None
    busy = hidden = 0
    for d in r.trace.devices:
        red, other = _crm.split(r, d)
        busy += _crm.busy_ns(red)
        hidden += hidden_ns(red, other)
    return 100.0 * (busy - hidden) / busy if busy else None
