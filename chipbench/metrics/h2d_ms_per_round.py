"""h2d_ms_per_round: the program's PhaseTimes "h2d" seconds accrued in
the window (the staged chunk's host-to-device copy, closed on the device
arrays), per round."""


def read(r):
    s = r.win["phases"].get("h2d")
    return s * 1e3 / r.rounds if s is not None and r.rounds else None
