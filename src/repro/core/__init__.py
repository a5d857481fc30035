from repro.core.client import make_local_train, make_fes_local_train
from repro.core.round import (make_round_step, make_train_loop,
                              make_train_step_for_lowering, init_state)
from repro.core import strategies


def __getattr__(name):
    # lazy back-compat re-export: simulation imports repro.exec.engine,
    # which imports repro.core — importing it eagerly here makes package
    # init order decide whether `import repro.exec.engine` works at all
    if name in ("FederatedSimulation", "History"):
        from repro.core import simulation
        return getattr(simulation, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
