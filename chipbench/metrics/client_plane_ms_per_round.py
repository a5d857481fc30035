"""client_plane_ms_per_round: device time of the round program's
operation events less its Mosaic kernel and collective events (the
local training of the selected clients, with the staging copies the
program makes), averaged over the chips, per round."""
from chipbench.trace import COLLECTIVE
from chipbench.metrics import _programs as P


def read(r):
    if r.trace is None or not r.rounds:
        return None
    names = P.kernel_ops(r)
    tot = sum(P.round_ops(r, d).select(
        lambda n: n not in names and not COLLECTIVE.match(n)).total_ns()
        for d in r.trace.devices)
    return tot / len(r.trace.devices) / 1e6 / r.rounds if tot else None
