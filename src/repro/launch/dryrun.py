"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production mesh and report memory/FLOPs/collectives (no real allocation).

MUST set the placeholder device count before any other import touches jax.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, FLConfig, ModelConfig, ShapeConfig
from repro.configs.registry import (ASSIGNED, LONG_CONTEXT_OK, get_arch,
                                    get_shape, pairs, serving_config)
from repro.core.round import make_train_step_for_lowering
from repro.launch.mesh import fl_view, make_production_mesh, serve_view
from repro.models.api import build_model, input_specs
from repro.sharding import specs as sh
from repro.utils.hlo import collective_stats

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "../../../experiments/artifacts/dryrun")


# ------------------------------------------------------------ builders -----

# Per-arch FL round geometry: big archs need fewer parallel cohorts (each
# cohort is a full model replica) and deeper microbatching to bound the
# activation-checkpoint stack. C * (params + grads + f32 staging) has to
# fit the pod; see EXPERIMENTS.md §Dry-run for the fit analysis.
ARCH_FL = {
    "minitron-8b": dict(cohorts=4, local_steps=8),   # §Perf H3: peak 13.4->7.2 GiB
    "llama3-405b": dict(cohorts=2, local_steps=16),
    "mistral-large-123b": dict(cohorts=2, local_steps=8),
    "qwen1.5-110b": dict(cohorts=2, local_steps=8),
    "mixtral-8x22b": dict(cohorts=2, local_steps=8),
    "phi3.5-moe-42b-a6.6b": dict(cohorts=4, local_steps=8),
}

# per-arch TP width on the training mesh (§Perf H2): rwkv6's 40 heads /
# zamba2's head layout shard evenly over 8, making the head reshape a
# LOCAL op instead of an all-gather of every projection output.
ARCH_MODEL_WIDTH = {
    "rwkv6-3b": 8,
    "zamba2-1.2b": 8,
}


def fl_for(arch: str) -> "FLConfig":
    return default_fl(**ARCH_FL.get(arch, {}))


def default_fl(cohorts: int = 4, local_steps: int = 4) -> FLConfig:
    """Dry-run FL config: the shape's global batch is one federated round's
    traffic, split into ``local_steps`` sequential microbatch SGD steps per
    cohort (paper: e=10 local epochs -> several local steps per round).
    Microbatching also bounds the activation-checkpoint stack: per-device
    live tokens = global_batch*seq/(cohorts*local_steps*dsub)."""
    return FLConfig(cohorts=cohorts, local_steps=local_steps,
                    algorithm="ama_fes", max_delay=0, p_limited=0.25)


def ep_factor(cfg: ModelConfig, n_model: int = 16) -> int:
    """Factorized (expert, etp) mesh — EVALUATED AND REFUTED for this
    workload (§Perf H1-it5): splitting the model axis regressed compute
    2.8x vs constraining the capacity dim onto the whole model axis,
    because the within-expert-TP layout conflicts with the dispatch
    layout on the narrow etp sub-axis. Kept (return 0 disables it) so the
    experiment is reproducible; the production scheme is H1-it4."""
    return 0


def train_lowering(cfg: ModelConfig, shape: ShapeConfig, mesh, fl: FLConfig):
    """Lower the federated round (train_step) on the FL mesh view."""
    model = build_model(cfg)
    fmesh = fl_view(mesh, fl.cohorts, expert_parallel=ep_factor(cfg),
                    model_width=ARCH_MODEL_WIDTH.get(cfg.name, 0))
    C = fmesh.shape["client"]
    steps = fl.local_steps
    b = shape.global_batch // (C * steps)
    if b == 0:
        raise ValueError(f"batch {shape.global_batch} too small for "
                         f"C={C} x steps={steps}")

    base = input_specs(cfg, shape)["batch"]
    batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((C, steps, b) + s.shape[1:], s.dtype),
        base)
    sched = {
        "limited": jax.ShapeDtypeStruct((C,), jnp.bool_),
        "delayed": jax.ShapeDtypeStruct((C,), jnp.bool_),
        "delays": jax.ShapeDtypeStruct((C,), jnp.int32),
        "data_sizes": jax.ShapeDtypeStruct((C,), jnp.float32),
    }
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    t_like = jax.ShapeDtypeStruct((), jnp.int32)

    p_sh = sh.params_shardings(params_like, cfg, fmesh, train=True)
    in_shardings = (
        p_sh,
        sh.replicated(t_like, fmesh),
        sh.batch_shardings(batch, fmesh, train=True),
        sh.sched_shardings(sched, fmesh),
    )
    step = make_train_step_for_lowering(model, fl)
    jitted = jax.jit(step, in_shardings=in_shardings,
                     out_shardings=(p_sh, None))
    with jax.set_mesh(fmesh):
        lowered = jitted.lower(params_like, t_like, batch, sched)
    return lowered


def prefill_lowering(cfg: ModelConfig, shape: ShapeConfig, mesh):
    model = build_model(cfg)
    smesh = serve_view(mesh, expert_parallel=ep_factor(cfg))
    batch = input_specs(cfg, shape)["batch"]
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = sh.params_shardings(params_like, cfg, smesh, train=False)
    b_sh = sh.batch_shardings(batch, smesh, train=False)

    jitted = jax.jit(model.prefill_logits, in_shardings=(p_sh, b_sh),
                     out_shardings=None)
    with jax.set_mesh(smesh):
        lowered = jitted.lower(params_like, batch)
    return lowered


def decode_lowering(cfg: ModelConfig, shape: ShapeConfig, mesh):
    model = build_model(cfg)
    smesh = serve_view(mesh, expert_parallel=ep_factor(cfg))
    ins = input_specs(cfg, shape)
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = sh.params_shardings(params_like, cfg, smesh, train=False)
    c_sh = sh.cache_shardings(ins["cache"], cfg, smesh)
    tok_sh = sh.batch_shardings(ins["token"], smesh, train=False)
    pos_sh = sh.batch_shardings(ins["position"], smesh, train=False)

    jitted = jax.jit(model.decode_step,
                     in_shardings=(p_sh, tok_sh, pos_sh, c_sh),
                     out_shardings=(None, c_sh))
    with jax.set_mesh(smesh):
        lowered = jitted.lower(params_like, ins["token"], ins["position"],
                               ins["cache"])
    return lowered


def build_lowering(arch: str, shape_name: str, mesh, fl: FLConfig = None,
                   cfg_overrides: dict = None):
    """Deploy lowering: scanned loops (the program you would actually run);
    memory_analysis is truthful. Roofline FLOPs come from the costing
    lowerings in benchmarks/costing.py (unrolled + depth-calibrated),
    because HloCostAnalysis counts scan bodies once."""
    shape = get_shape(shape_name)
    cfg = get_arch(arch) if shape.kind == "train" else serving_config(arch)
    if cfg_overrides:
        cfg = cfg.with_(**cfg_overrides)
    if shape.kind == "train":
        return train_lowering(cfg, shape, mesh, fl or default_fl())
    if shape.kind == "prefill":
        return prefill_lowering(cfg, shape, mesh)
    return decode_lowering(cfg, shape, mesh)


# ------------------------------------------------------------ analysis -----

def analyse(lowered, compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    out = {
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll.total_bytes,
        "collectives": {k: {"n": coll.counts[k], "bytes": coll.bytes_[k]}
                        for k in coll.counts},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
    }
    return out


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             fl: FLConfig = None, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered = build_lowering(arch, shape_name, mesh, fl or fl_for(arch))
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec = analyse(lowered, compiled)
    rec.update(arch=arch, shape=shape_name,
               mesh="2x16x16" if multi_pod else "16x16",
               lower_s=round(t1 - t0, 1), compile_s=round(t2 - t1, 1))
    if verbose:
        mem = rec["memory"]
        arg = (mem["argument_bytes"] or 0) / 2**30
        tmp = (mem["temp_bytes"] or 0) / 2**30
        print(f"[{arch} x {shape_name} @ {rec['mesh']}] "
              f"flops={rec['hlo_flops']:.3e} bytes={rec['hlo_bytes']:.3e} "
              f"coll={rec['collective_bytes']:.3e}B "
              f"mem(arg={arg:.2f}GiB temp={tmp:.2f}GiB) "
              f"lower={rec['lower_s']}s compile={rec['compile_s']}s")
    return rec


def save_record(rec: dict, tag: str = ""):
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh'].replace('x','-')}{tag}.json"
    with open(os.path.join(ARTIFACT_DIR, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every assigned (arch x shape) pair")
    ap.add_argument("--cohorts", type=int, default=4)
    args = ap.parse_args()

    fl = default_fl(args.cohorts) if args.cohorts != 4 else None
    todo = []
    if args.all:
        todo = pairs()
    else:
        archs = [args.arch] if args.arch else ASSIGNED
        shapes = [args.shape] if args.shape else list(SHAPES)
        for a in archs:
            for s in shapes:
                skip = s == "long_500k" and not LONG_CONTEXT_OK[a]
                todo.append((a, s, skip))

    ok = fail = skipped = 0
    for arch, shape_name, skip in todo:
        if skip:
            print(f"[{arch} x {shape_name}] SKIP (full attention at 524k; "
                  f"see DESIGN.md)")
            skipped += 1
            continue
        try:
            rec = run_pair(arch, shape_name, multi_pod=args.multi_pod, fl=fl)
            save_record(rec)
            ok += 1
        except Exception as e:  # a failure here is a bug in the system
            print(f"[{arch} x {shape_name}] FAILED: {type(e).__name__}: "
                  f"{str(e)[:300]}")
            fail += 1
    print(f"\ndry-run done: {ok} ok, {fail} failed, {skipped} skipped")
    return 1 if fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
