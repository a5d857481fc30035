"""setup_s: process start to the start of the window (data, weights,
compile or cache load, warm-up and the rounds the reference replays)."""


def read(r):
    return r.setup_s
