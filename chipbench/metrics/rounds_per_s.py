"""rounds_per_s: rounds completed in the window over the seconds from the
window's start to the host timestamp of the last round completed."""


def read(r):
    return r.rounds / r.window_s if r.rounds else None
