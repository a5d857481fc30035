"""eval_ms_per_round: the program's PhaseTimes "eval" seconds accrued in
the window (dispatch to results on the host), per round."""


def read(r):
    s = r.win["phases"].get("eval")
    return s * 1e3 / r.rounds if s is not None and r.rounds else None
