"""Layer 2: jaxpr- and lowering-level invariants, driven from the
registries.

Where layer 1 reads source, this layer traces the REAL programs: for
every registered ``ServerStrategy`` x a small config matrix it builds
the engine's actual ``make_train_loop`` (the same callable ChunkRunner
jits) against abstract inputs and asserts

  FED201 donation-aliasing        the donated round carry actually
                                  aliases in the lowering (every params
                                  leaf carries ``tf.aliasing_output``) —
                                  a dropped donation silently doubles
                                  the HBM watermark at LLM scale
  FED202 effectful-scan-primitive no callback/infeed/outfeed primitives
                                  and no JAX effects inside the round
                                  scan body (a debug print in the scan
                                  is a per-chunk host sync)
  FED203 carry-stability          one round step maps the state pytree
                                  onto exactly its own structure/shapes/
                                  dtypes (what scan and bit-identical
                                  resume both require)
  FED204 kernel-oracle-parity     every public Pallas kernel entry in
                                  ``repro.kernels`` has a matching
                                  ``ref.*_math`` / ``*_ref`` oracle with
                                  the same positional signature (the
                                  contract PRs 4 and 9 kept by hand)

Everything traces against ``jax.ShapeDtypeStruct`` inputs — no data is
materialized and nothing is compiled, so the whole layer is a few
seconds of tracing.
"""
from __future__ import annotations

import ast
import inspect

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding

# no effectful primitive belongs inside the fused round scan
_EFFECT_PRIMS = ("callback", "infeed", "outfeed", "debug_print",
                 "host_local_array_to_global_array")


# ------------------------------------------------------------- harness --

def _tiny_fl(**kw):
    from repro.configs.base import FLConfig
    base = dict(num_clients=8, clients_per_round=4, cohorts=4,
                local_epochs=1, local_batch_size=2, seed=0)
    base.update(kw)
    return FLConfig(**base)


def config_matrix():
    """(label, FLConfig) per registered strategy, plus the telemetry and
    compressed-uplink planes on the default strategy — the row set every
    layer-2 rule traces."""
    from repro.core import strategies
    cfgs, seen = [], set()
    for name in strategies.names():
        cls = strategies.get(name)
        if cls in seen:            # registry aliases (ama / ama_fes)
            continue
        seen.add(cls)
        kw = {"algorithm": name}
        if name == "async_ama":
            kw.update(max_delay=3, p_delay=0.4)
        cfgs.append((name, _tiny_fl(**kw)))
    cfgs.append(("ama+extended_metrics",
                 _tiny_fl(algorithm="ama", extended_metrics=True)))
    cfgs.append(("ama+comm_q8", _tiny_fl(algorithm="ama", comm_plane="q8")))
    return cfgs


class TraceHarness:
    """Abstract inputs + the engine's real train loop for one config."""

    def __init__(self, fl, n_rounds: int = 2, model=None):
        from repro.configs.registry import ARCHS
        from repro.core import strategies
        from repro.core.round import init_state, make_round_step
        from repro.models.api import build_model
        self.fl = fl
        self.model = model or build_model(ARCHS["paper-cnn"])
        self.strategy = strategies.resolve(fl)
        self.n = n_rounds
        self.state = jax.eval_shape(
            lambda: init_state(self.model, fl, jax.random.PRNGKey(fl.seed),
                               self.strategy))
        C, b = fl.clients_per_round, fl.local_batch_size
        steps = 1
        sds = jax.ShapeDtypeStruct
        self.batch = {
            "image": sds((n_rounds, C, steps, b, 28, 28, 1), jnp.float32),
            "label": sds((n_rounds, C, steps, b), jnp.int32)}
        self.scheds = {
            "limited": sds((n_rounds, C), jnp.bool_),
            "delayed": sds((n_rounds, C), jnp.bool_),
            "delays": sds((n_rounds, C), jnp.int32),
            "data_sizes": sds((n_rounds, C), jnp.float32)}
        self._round_step = make_round_step(self.model, fl, self.strategy)

    def loop_args(self):
        args = [self.state, self.batch, self.scheds]
        if getattr(self.fl, "extended_metrics", False):
            args.append({"params": self.state["params"],
                         "aux": self.state["aux"]})
        return args

    def train_loop(self, donate: bool = True):
        from repro.core.round import make_train_loop
        return make_train_loop(self.model, self.fl, self.strategy,
                               per_round_batch=True, donate=donate)

    def lowered_text(self, donate: bool = True) -> str:
        return self.train_loop(donate).lower(*self.loop_args()).as_text()

    def jaxpr(self):
        return jax.make_jaxpr(self.train_loop())(*self.loop_args())

    def round_step_shapes(self):
        row = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
            (self.batch, self.scheds))
        return jax.eval_shape(self._round_step, self.state, row[0], row[1])


# --------------------------------------------------------------- rules --

def check_donation_aliasing(cfgs=None, *, donate: bool = True,
                            model=None) -> list[Finding]:
    """FED201: the lowering must report input-output aliasing for every
    donated params leaf (``tf.aliasing_output`` on the entry args)."""
    findings = []
    for label, fl in (cfgs or config_matrix()):
        h = TraceHarness(fl, model=model)
        txt = h.lowered_text(donate=donate)
        n_alias = txt.count("tf.aliasing_output")
        n_expected = len(jax.tree.leaves(h.state["params"]))
        if n_alias < n_expected:
            findings.append(Finding(
                rule="FED201", path=f"<trace:{label}>", line=0,
                message=(f"train_loop lowering aliases {n_alias} buffers "
                         f"but the donated carry has {n_expected} params "
                         "leaves — donation is declared but not taking "
                         "effect (the round carry would be copied every "
                         "chunk; check donate_argnums and that no extra "
                         "consumer keeps the carry alive)")))
    return findings


def _sub_jaxprs(eqn):
    """(maybe-closed, raw) jaxpr pairs referenced by one equation's
    params (pjit/scan/cond/custom_* all stash theirs differently)."""
    out = []
    vals = []
    for v in eqn.params.values():
        vals.extend(v if isinstance(v, (list, tuple)) else [v])
    for v in vals:
        if hasattr(v, "jaxpr") and hasattr(v, "eqns"):
            out.append((v, v.jaxpr))           # ClosedJaxpr
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            out.append((v, v.jaxpr))           # ClosedJaxpr (no .eqns)
        elif hasattr(v, "eqns"):
            out.append((v, v))                 # raw Jaxpr
    return out


def _walk_scan_bodies(jaxpr):
    """Yield the (maybe-closed) body jaxpr of every scan, at any depth."""
    for eqn in jaxpr.eqns:
        for closed, raw in _sub_jaxprs(eqn):
            if eqn.primitive.name == "scan":
                yield closed
            yield from _walk_scan_bodies(raw)


def check_scan_effects(cfgs=None, *, model=None,
                       jaxpr_fn=None) -> list[Finding]:
    """FED202: no effectful primitives / JAX effects inside the fused
    round scan. ``jaxpr_fn(label, fl) -> jaxpr`` is injectable so the
    fixture tests can feed a deliberately dirty program."""
    findings = []
    for label, fl in (cfgs or config_matrix()):
        jx = (jaxpr_fn(label, fl) if jaxpr_fn
              else TraceHarness(fl, model=model).jaxpr())
        for body in _walk_scan_bodies(jx.jaxpr):
            effects = getattr(body, "effects", None) or getattr(
                getattr(body, "jaxpr", body), "effects", set())
            if effects:
                findings.append(Finding(
                    rule="FED202", path=f"<trace:{label}>", line=0,
                    message=(f"scan body carries JAX effects {effects} — "
                             "an effectful op inside the fused round "
                             "scan forces per-round host sync and "
                             "breaks donation/CSE isolation")))
            raw = getattr(body, "jaxpr", body)
            for eqn in raw.eqns:
                if any(tok in eqn.primitive.name for tok in _EFFECT_PRIMS):
                    findings.append(Finding(
                        rule="FED202", path=f"<trace:{label}>", line=0,
                        message=(f"effectful primitive "
                                 f"'{eqn.primitive.name}' inside the "
                                 "round scan body")))
    return findings


def check_carry_stability(cfgs=None, *, model=None,
                          step_fn=None) -> list[Finding]:
    """FED203: round_step(state, ...) must return a state with exactly
    the input's tree structure, shapes and dtypes. ``step_fn(h) ->
    (out_state_shapes, in_state_shapes)`` is injectable for fixtures."""
    findings = []
    for label, fl in (cfgs or config_matrix()):
        h = TraceHarness(fl, model=model)
        if step_fn is not None:
            out_state, in_state = step_fn(h)
        else:
            out_state = h.round_step_shapes()[0]
            in_state = h.state
        ti, to = jax.tree.structure(in_state), jax.tree.structure(out_state)
        if ti != to:
            findings.append(Finding(
                rule="FED203", path=f"<trace:{label}>", line=0,
                message=(f"round carry tree structure changes across a "
                         f"round: {ti} -> {to} — lax.scan and resume "
                         "both need a fixed carry")))
            continue
        for (keys, b), a in zip(
                jax.tree_util.tree_flatten_with_path(in_state)[0],
                jax.tree.leaves(out_state)):
            if a.shape != b.shape or a.dtype != b.dtype:
                findings.append(Finding(
                    rule="FED203", path=f"<trace:{label}>", line=0,
                    message=(f"carry leaf {jax.tree_util.keystr(keys)} "
                             f"unstable across a round: "
                             f"{b.shape}/{b.dtype} -> "
                             f"{a.shape}/{a.dtype}")))
    return findings


# kernel entries whose oracle does not follow the ``<base>_math`` /
# ``<base>_ref`` naming derivable from the kernel name
_ORACLE_CANDIDATES = ("{base}_math", "{base}_ref", "{name}_math",
                      "{name}_ref")


def _kernel_entries(module) -> list[tuple[str, list[str]]]:
    """Public top-level functions of ``module`` that dispatch a
    ``pallas_call`` — directly or through the module's private
    (``_``-prefixed) helpers — with their positional parameter names
    (from the source AST — robust to jit wrappers)."""
    src = inspect.getsource(module)
    tree = ast.parse(src)
    funcs = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}

    def callees(node):
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                if isinstance(n.func, ast.Attribute):
                    yield n.func.attr
                elif isinstance(n.func, ast.Name):
                    yield n.func.id

    dispatching = {name for name, node in funcs.items()
                   if "pallas_call" in set(callees(node))}
    grew = True
    while grew:                     # close over the private helpers
        grew = False
        for name, node in funcs.items():
            if name not in dispatching and any(
                    c.startswith("_") and c in dispatching
                    for c in callees(node)):
                dispatching.add(name)
                grew = True
    out = []
    for name, node in funcs.items():
        if name in dispatching and not name.startswith("_"):
            pos = [a.arg for a in node.args.posonlyargs + node.args.args]
            out.append((name, pos))
    return out


def check_kernel_oracles(kernel_modules=None,
                         ref_module=None) -> list[Finding]:
    """FED204: every Pallas kernel entry must have a ref oracle with an
    identical positional signature. Both the kernel module list and the
    oracle module are injectable so a fixture can rename an oracle."""
    if kernel_modules is None:
        from repro.kernels import server_plane
        kernel_modules = [server_plane]
    if ref_module is None:
        from repro.kernels import ref as ref_module
    findings = []
    for mod in kernel_modules:
        for name, kpos in _kernel_entries(mod):
            base = name[:-5] if name.endswith("_flat") else name
            cands = []
            for pat in _ORACLE_CANDIDATES:
                c = pat.format(base=base, name=name)
                if c not in cands:
                    cands.append(c)
            oracle = next((getattr(ref_module, c) for c in cands
                           if hasattr(ref_module, c)), None)
            where = f"{mod.__name__}.{name}"
            if oracle is None:
                findings.append(Finding(
                    rule="FED204", path=f"<kernel:{where}>", line=0,
                    message=(f"no oracle for Pallas kernel '{name}' — "
                             f"expected one of {cands} in "
                             f"{getattr(ref_module, '__name__', 'ref')} "
                             "(the kernel's only correctness ground "
                             "truth; see kernels/ref.py)")))
                continue
            sig = inspect.signature(oracle)
            opos = [p.name for p in sig.parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY,
                                  p.POSITIONAL_OR_KEYWORD)]
            if opos != kpos:
                findings.append(Finding(
                    rule="FED204", path=f"<kernel:{where}>", line=0,
                    message=(f"oracle '{oracle.__name__}' positional "
                             f"signature {opos} does not match kernel "
                             f"'{name}' positional signature {kpos} — "
                             "parity tests would silently compare "
                             "misaligned arguments")))
    return findings


JAXPR_RULES = {
    "FED201": check_donation_aliasing,
    "FED202": check_scan_effects,
    "FED203": check_carry_stability,
    "FED204": check_kernel_oracles,
}


def run(select=None) -> list[Finding]:
    """All (selected) layer-2 rules over the real registries. The config
    matrix is traced once and shared by the rules that need it."""
    findings = []
    selected = [rid for rid in JAXPR_RULES
                if select is None or rid in select]
    if not selected:
        return findings
    cfgs = config_matrix() if any(r != "FED204" for r in selected) else None
    for rid in selected:
        if rid == "FED204":
            findings.extend(check_kernel_oracles())
        else:
            findings.extend(JAXPR_RULES[rid](cfgs))
    return findings
