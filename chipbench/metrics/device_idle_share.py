"""device_idle_share: 1 - (union of the intervals in which an operation
ran on the device / the traced window), averaged over the chips, in
percent."""


def read(r):
    if r.trace is None or r.trace.window is None:
        return None
    return 100.0 * (1.0 - r.busy_s() / (r.trace.window_ns() / 1e9))
