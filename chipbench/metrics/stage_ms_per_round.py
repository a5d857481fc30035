"""stage_ms_per_round: the program's PhaseTimes "stage" seconds accrued
in the window (host staging on the prefetch thread), per round."""


def read(r):
    s = r.win["phases"].get("stage")
    return s * 1e3 / r.rounds if s is not None and r.rounds else None
