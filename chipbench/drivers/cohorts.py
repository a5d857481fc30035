"""Driver ``cohorts``: cross-silo fine-tuning as its users run it.

The pod path of ``launch/train.py`` (``build_pod``: the model, the round
state, the strategy, the environment and a ``ChunkRunner`` over
``engine_mesh``) trains ``fl.cohorts`` silos, one a chip, every round on
fresh tokens of their own (``chipbench/gen/tokens.py``), one round a
``run_chunk``; a round is done when its loss is on the host.

Set-up, in order: the seed's weights (made on a chip, kept on the host);
the program's first rounds; then rounds until one loads no program. The
window runs rounds until the deadline. The plain f32 reference replays
the first rounds, its silos split over the chips, only when the numbers
are asked for, after the program is released (``run.measure``): the two
never share a chip's memory, and the reference is not set-up.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare_lm, flops_lm
from chipbench.gen import tokens as gen_tokens
from chipbench.gen import weights
from chipbench.reference import lm as ref_lm
from chipbench.reference import xsilo
from repro.launch.train import build_pod

#: rounds that set-up runs and the reference replays
CHECK_ROUNDS = 2
#: extra warm rounds allowed while programs still load
MAX_WARM = 5
#: the configuration's keys the program's model config takes
WIDTHS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
          "mlp_act", "norm", "rope_theta", "rotary_frac", "dtype",
          "param_dtype", "num_layers", "vocab_size", "fes_tail_layers")


class Session:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.config, ctx.traffic
        self.seed = ctx.seed
        # the program's numpy streams take 32-bit seeds
        self.prog_seed = ctx.seed % 2**31
        self.devices = jax.devices()[:ctx.chips]
        #: silos the reference trains at a time (all by default)
        self.group = None
        self._ref = None

    def model_config(self):
        """The program's config of ``arch`` with the file's values."""
        from repro.configs.registry import get_arch
        c = self.cfg
        return get_arch(c["arch"]).with_(**{k: c[k] for k in WIDTHS})

    def fl(self):
        from repro.configs.base import FLConfig
        return FLConfig(**self.traffic["fl"], seed=self.prog_seed)

    # ------------------------------------------------------------------
    def setup(self):
        from repro.launch.mesh import engine_mesh
        from repro.sharding.ctx import server_shardings
        mc, fl = self.model_config(), self.fl()
        self.make_weights()
        mesh = engine_mesh(fl.cohorts)
        self.pod = build_pod(mc, fl, mesh=mesh, params=jax.device_put(
            self.p0, server_shardings(self.p0, mesh)))
        have = jax.tree.map(lambda x: (x.shape, x.dtype),
                            self.pod.state["params"])
        want = jax.tree.map(lambda x: (x.shape, x.dtype), self.p0)
        if have != want:
            raise ValueError(f"the program's weights {have} are not the "
                             f"configuration's {want}")
        self.state, self.t = self.pod.state, 0
        self.ctx.log(f"program: mesh {dict(mesh.shape)}")
        got = {"loss": []}
        for r in range(CHECK_ROUNDS):
            got["loss"].append(float(self._round()["loss"][0]))
            got[f"p{r + 1}"] = jax.device_get(self.state["params"])
        self.got = got
        self.ctx.log(f"{CHECK_ROUNDS} rounds for the check, losses "
                     f"{got['loss']}")
        compiles = [self.ctx.compiles()]
        for _ in range(MAX_WARM):
            self._round()
            compiles.append(self.ctx.compiles())
            if compiles[-1] == compiles[-2]:
                break
        self.ctx.log(f"warm: program loads after each round {compiles}")

    def make_weights(self):
        """The seed's weights, made on a chip and kept on the host."""
        w = weights.make(ref_lm.param_specs(self.cfg), self.seed)
        self.p0 = jax.device_get(w)
        jax.tree.map(lambda a: a.delete(), w)
        n = sum(x.size for x in jax.tree.leaves(self.p0))
        self.ctx.log(f"weights: {n} a silo, on the host")

    def _round(self) -> dict:
        """One round of the program on fresh tokens: its metrics."""
        tr = self.traffic
        g, fl = tr["generator"], tr["fl"]
        sb = self.pod.environment.batch(self.t, 1)
        toks = gen_tokens.round_tokens(
            self.seed, self.t, sb["selected"][0],
            (fl["local_steps"], g["batch"], g["seq"]), g["vocab"],
            g["zipf_exponent"])
        self.state, m = self.pod.runner.run_chunk(
            self.state, {"tokens": toks[None]}, sb)
        self.limited = [bool(x) for x in sb["limited"][0]]
        self.t += 1
        return m

    # ------------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        done, failed, limited = [], 0, []
        timer = self.pod.runner.timer
        phases0 = dict(timer.seconds)
        with self.ctx.span("chipbench_window"):
            start = time.perf_counter()
            while True:
                m = self._round()
                now = time.perf_counter()
                done.append(now)
                limited.append(self.limited)
                failed += int(np.sum(~np.isfinite(m["loss"])))
                if now >= start + seconds:
                    break
        phases = {k: v - phases0.get(k, 0.0)
                  for k, v in timer.seconds.items()}
        g, fl = self.traffic["generator"], self.traffic["fl"]
        req = sum(flops_lm.round_flops(self.cfg, fl["local_steps"],
                                       g["batch"], g["seq"], lim)
                  for lim in limited)
        return {"start": start, "done": done, "attempted": len(done),
                "failed": failed, "phases": phases, "flops": req}

    def programs(self) -> dict:
        """Compiled text of the round program the window drove."""
        return {"round": self.pod.runner.lower_last().compile().as_text()}

    def release(self):
        if self.state is not None:
            jax.tree.map(lambda a: a.delete(), self.state["params"])
        self.state = self.pod = None

    # ------------------------------------------------------------------
    def reference(self, dtype=jnp.float32, fault=None) -> dict:
        return xsilo.run(self.traffic, self.cfg, self.seed, self.prog_seed,
                         self.p0, CHECK_ROUNDS, self.devices, dtype, fault,
                         self.group)

    def numbers(self, got=None) -> dict:
        """The compared numbers of ``got`` (the program's first rounds
        by default) against the f32 reference."""
        return compare_lm.numbers(self.p0, self._f32_reference(),
                                  self.got if got is None else got)

    def leaves(self, got=None) -> dict:
        """Per-leaf gaps of the first update, for a look by hand."""
        return compare_lm.leaf_report(self.p0, self._f32_reference(),
                                      self.got if got is None else got)

    def _f32_reference(self) -> dict:
        if self._ref is None:
            self._ref = self.reference()
            gc.collect()
            self.ctx.log(f"reference: {CHECK_ROUNDS} rounds, losses "
                         f"{self._ref['loss']}")
        return self._ref

    def control(self) -> dict:
        """The reference in bfloat16 (weights and arithmetic) in the
        program's place."""
        return self.numbers(self.reference(jnp.bfloat16))

    def faults(self) -> dict:
        """The first rounds of the reference, in the program's place, with
        each fault planted that this cell can have."""
        return {f: self.reference(fault=f) for f in xsilo.FAULTS}
