"""round_ms_p90: the 90th percentile of the intervals between consecutive
round completions, the first measured from the window's start, over all
rounds of the window (a stall shows)."""
import numpy as np


def read(r):
    t = np.asarray([r.win["start"]] + list(r.win["done"]))
    if len(t) < 11:
        return None
    return float(np.percentile(np.diff(t), 90) * 1e3)
