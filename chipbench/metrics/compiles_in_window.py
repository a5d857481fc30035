"""compiles_in_window: programs loaded (compiled or read from the
persistent cache) during the window, from jax.monitoring's
backend-compile events."""


def read(r):
    return r.win["compiles"]
