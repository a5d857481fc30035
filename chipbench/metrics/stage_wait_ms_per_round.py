"""stage_wait_ms_per_round: the program's PhaseTimes "stage_wait" seconds
accrued in the window (the main thread waiting for its staged chunk),
per round."""


def read(r):
    s = r.win["phases"].get("stage_wait")
    return s * 1e3 / r.rounds if s is not None and r.rounds else None
