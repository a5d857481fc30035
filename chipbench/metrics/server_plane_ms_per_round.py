"""server_plane_ms_per_round: device time of the round program's Mosaic
(Pallas) kernel events, averaged over the chips, per round. Nothing to
read where the server plane runs in XLA (no kernel in the program). A
kernel that the compiled program holds and the window's trace never
shows is an error: the trace names its ops otherwise than the program
does, and the client plane would take the kernel's time unseen."""
from chipbench.metrics import _programs as P


def kernel_ns(r) -> float | None:
    names = P.kernel_ops(r)
    if r.trace is None or not names or not r.rounds:
        return None
    tot = sum(P.round_ops(r, d).select(lambda n: n in names).total_ns()
              for d in r.trace.devices)
    if not tot:
        raise LookupError(f"the round program holds the kernels "
                          f"{sorted(names)}, the trace shows none of them")
    return tot / len(r.trace.devices)


def read(r):
    ns = kernel_ns(r)
    return ns / 1e6 / r.rounds if ns else None
