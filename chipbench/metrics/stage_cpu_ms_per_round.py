"""stage_cpu_ms_per_round: the program's PhaseTimes counter "stage_cpu"
accrued in the window (the staging thread's CPU seconds inside "stage"),
per round."""


def read(r):
    s = r.win["phases"].get("stage_cpu")
    return s * 1e3 / r.rounds if s is not None and r.rounds else None
