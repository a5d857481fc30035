"""Driver ``paper_sim``: the paper's simulation as its users run it.

One ``SimulationEngine`` is built from the seed's images and weights and
driven through ``SimulationEngine.run``: staging on the prefetch thread,
one round per dispatch, the evaluation after every round. Set-up runs
the first rounds through ``run`` (they compile, and the reference
replays them) and warms until a round loads no program. The window is
one uninterrupted ``run`` that a logger hook of the benchmark's own
stops at the deadline; a round counts as done when its evaluation is
back on the host.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, flops
from chipbench.gen import images, weights
from chipbench.reference import cnn as ref_cnn
from chipbench.reference import paper as ref_paper

#: rounds that set-up runs and the reference replays
CHECK_ROUNDS = 3
#: extra warm rounds allowed while programs still load
MAX_WARM = 5
#: the window's run is sized to outlast the deadline at this rate
ROUNDS_PER_S_CAP = 200


class StopWindow(Exception):
    """Raised from the logger hook at the deadline."""


class _Hooks:
    """The ``MetricsLogger`` surface ``SimulationEngine`` calls."""

    def __init__(self):
        self.on_round = self.on_eval = None

    def header(self, *args, **kwargs):
        pass

    def phases(self, times):
        pass

    def rounds(self, t0, metrics):
        self.on_round(t0, metrics)

    def eval(self, t, acc, loss):
        self.on_eval(t, acc, loss)


class Session:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic
        self.seed = ctx.seed
        # the program's numpy streams take 32-bit seeds
        self.prog_seed = ctx.seed % 2**31
        self.hooks = _Hooks()
        self._ref = None

    # ------------------------------------------------------------------
    def setup(self):
        from repro.configs.base import FLConfig
        from repro.configs.registry import get_arch
        from repro.data.partition import shard_partition
        from repro.data.pipeline import build_clients
        from repro.exec.engine import SimulationEngine
        from repro.launch.mesh import engine_mesh
        from repro.models.api import build_model

        tr, g = self.traffic, self.traffic["generator"]
        self.train, self.test = images.generate(
            self.seed, g["n_train"], g["n_test"], g["classes"])
        self.ctx.log(f"data: {g['n_train']} train, {g['n_test']} test images")
        fl = FLConfig(**tr["fl"], seed=self.prog_seed)
        parts = shard_partition(self.train["label"], fl.num_clients,
                                seed=self.prog_seed)
        model = build_model(get_arch(self.cfg["arch"]))
        self.engine = SimulationEngine(
            model, fl, build_clients(self.train, parts), self.test,
            mesh=engine_mesh(fl.clients_per_round), logger=self.hooks)
        w = weights.make(ref_cnn.param_specs(self.cfg), self.seed)
        have = jax.tree.map(lambda x: (x.shape, x.dtype),
                            self.engine.state["params"])
        want = jax.tree.map(lambda x: (x.shape, x.dtype), w)
        if have != want:
            raise ValueError(f"the program's weights {have} are not the "
                             f"configuration's {want}")
        self.p0 = jax.device_get(w)
        self.engine.state = {**self.engine.state, "params": w}
        self.steps = self.engine._steps_per_round()
        self.ctx.log(f"engine: {self.steps} local steps a round")

        got = {"loss": [], "eval_loss": []}
        compiles = []

        def on_round(t0, metrics):
            if t0 < CHECK_ROUNDS:
                got["loss"].extend(float(x) for x in metrics["loss"])
            if t0 + 1 in (1, CHECK_ROUNDS):
                got[f"p{t0 + 1}"] = jax.device_get(self.engine.state["params"])

        def on_eval(t, acc, loss):
            if t <= CHECK_ROUNDS:
                got["eval_loss"].append(float(loss))
            compiles.append(self.ctx.compiles())

        self.hooks.on_round, self.hooks.on_eval = on_round, on_eval
        self.engine.run(rounds=CHECK_ROUNDS, eval_every=1)
        self.got = got
        self.ctx.log(f"{CHECK_ROUNDS} rounds for the check")
        for _ in range(MAX_WARM):
            if len(compiles) > 1 and compiles[-1] == compiles[-2]:
                break
            self.engine.run(rounds=1, eval_every=1)
        self.ctx.log(f"warm: {len(compiles)} rounds, program loads "
                     f"after each {compiles}")

    # ------------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        done, failed, ts = [], [0], []
        deadline = float("inf")

        def on_round(t0, metrics):
            ts.extend(range(t0, t0 + len(metrics["loss"])))
            failed[0] += int(np.sum(~np.isfinite(metrics["loss"])))

        def on_eval(t, acc, loss):
            now = time.perf_counter()
            done.append(now)
            if now >= deadline:
                raise StopWindow

        self.hooks.on_round, self.hooks.on_eval = on_round, on_eval
        phases0 = dict(self.engine.timer.seconds)
        with self.ctx.span("chipbench_window"):
            start = time.perf_counter()
            deadline = start + seconds
            try:
                self.engine.run(rounds=int(ROUNDS_PER_S_CAP * seconds),
                                eval_every=1)
            except StopWindow:
                pass
        phases = {k: v - phases0.get(k, 0.0)
                  for k, v in self.engine.timer.seconds.items()}
        fl = self.engine.fl
        req = sum(flops.cnn_round(self.cfg, self.steps, fl.local_batch_size,
                                  list(self.engine.env.round(t).limited),
                                  len(self.test["label"]))
                  for t in ts[:len(done)])
        return {"start": start, "done": done, "attempted": len(ts),
                "failed": failed[0], "phases": phases, "flops": req}

    def programs(self) -> dict:
        """Compiled text of the round program the window drove."""
        return {"round": self.engine.runner.lower_last().compile().as_text()}

    def release(self):
        self.engine.state = None
        self.engine = None

    # ------------------------------------------------------------------
    def reference(self, dtype=jnp.float32, fault=None) -> dict:
        return ref_paper.run(self.traffic, self.prog_seed, self.train,
                             self.test, self.p0, CHECK_ROUNDS, dtype, fault)

    def numbers(self, got=None) -> dict:
        """The compared numbers of ``got`` (the program's first rounds
        by default) against the f32 reference."""
        return compare.training_numbers(self.p0, self._f32_reference(),
                                        self.got if got is None else got,
                                        ref_cnn.output_bias(self.cfg))

    def leaves(self, got=None) -> dict:
        """Per-leaf gaps of the first update, for a look by hand."""
        return compare.leaf_report(self.p0, self._f32_reference(),
                                   self.got if got is None else got)

    def _f32_reference(self) -> dict:
        if self._ref is None:
            self._ref = self.reference()
        return self._ref

    def control(self) -> dict:
        """The reference in bfloat16 in the program's place."""
        return self.numbers(self.reference(jnp.bfloat16))

    def faults(self) -> dict:
        """The first rounds of the reference, in the program's place, with
        each fault planted that this cell can have."""
        return {f: self.reference(fault=f) for f in ("half_batch", "label")}
