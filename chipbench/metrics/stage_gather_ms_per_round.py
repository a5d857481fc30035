"""stage_gather_ms_per_round: the program's PhaseTimes "stage_gather"
seconds accrued in the window (the gather of the staged chunk, inside
"stage"), per round."""


def read(r):
    s = r.win["phases"].get("stage_gather")
    return s * 1e3 / r.rounds if s is not None and r.rounds else None
