"""fedlint (repro.analysis) — fixture per rule: one that FIRES and one
clean near-miss, plus layer-2 checks against the real engine lowering
and the CLI gate contract CI relies on."""
import json
import os
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_rules as jr
from repro.analysis import run_paths
from repro.analysis.ast_rules import run_file

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ast(src, path="fixture/mod.py", select=None):
    """Unsuppressed findings of the AST layer over a fixture source."""
    fs = run_file(path, textwrap.dedent(src), select)
    return [f for f in fs if not f.suppressed]


# ----------------------------------------------------------- FED101 --

_DONATE_FIRE = """
    import jax
    f = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    def use(buf):
        out = f(buf)
        return out + buf
"""

_DONATE_CLEAN = """
    import jax
    f = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    def use(buf):
        buf = f(buf)
        return buf + 1
"""


def test_fed101_use_after_donate_fires():
    fs = _ast(_DONATE_FIRE, select={"FED101"})
    assert [f.rule for f in fs] == ["FED101"]
    assert "'buf'" in fs[0].message and "line 5" in fs[0].message


def test_fed101_same_statement_reassign_is_clean():
    assert _ast(_DONATE_CLEAN, select={"FED101"}) == []


def test_fed101_compound_and_nested_defs_are_not_misattributed():
    # regression: the serving engine's while-loop prefill and nested
    # admit_wave closures both reassign the donated buffer in-statement
    src = """
        import jax
        class E:
            def __init__(self):
                self.pf = jax.jit(lambda p, c: (p, c), donate_argnums=(1,))
            def prefill(self, cache, n):
                for _ in range(n):
                    logits, cache = self.pf(0, cache)
                jax.block_until_ready(cache)
                return logits, cache
            def run(self, kv):
                def wave(kv):
                    kv = self.pf(0, kv)[1]
                    return kv
                return wave(kv) + wave(kv)
    """
    assert _ast(src, select={"FED101"}) == []


def test_fed101_donation_inside_loop_read_later_in_loop_fires():
    src = """
        import jax
        f = jax.jit(lambda x: x, donate_argnums=(0,))
        def use(buf, n):
            for _ in range(n):
                out = f(buf)
                print(buf)
    """
    fs = _ast(src, select={"FED101"})
    assert [f.rule for f in fs] == ["FED101"]


# ----------------------------------------------------------- FED102 --

_NONDET = """
    import jax
    import numpy as np
    @jax.jit
    def step(x):
        return x * np.random.rand()
"""


def test_fed102_host_rng_in_traced_code_fires():
    fs = _ast(_NONDET, select={"FED102"})
    assert [f.rule for f in fs] == ["FED102"]
    assert "np.random.rand" in fs[0].message


def test_fed102_host_side_rng_is_clean():
    src = """
        import numpy as np
        def host_plan():
            return np.random.rand()
    """
    assert _ast(src, select={"FED102"}) == []


def test_fed102_env_host_plane_is_allowlisted():
    assert _ast(_NONDET, path="src/repro/env/base.py",
                select={"FED102"}) == []


# ----------------------------------------------------------- FED103 --

def test_fed103_closure_mutation_in_scan_body_fires():
    src = """
        import jax
        acc = []
        def loop(c, xs):
            def body(c, x):
                acc.append(x)
                return c, x
            return jax.lax.scan(body, c, xs)
    """
    fs = _ast(src, select={"FED103"})
    assert [f.rule for f in fs] == ["FED103"]
    assert "acc.append" in fs[0].message


def test_fed103_local_mutation_in_scan_body_is_clean():
    src = """
        import jax
        def loop(c, xs):
            def body(c, x):
                parts = []
                parts.append(x)
                return c, sum(parts)
            return jax.lax.scan(body, c, xs)
    """
    assert _ast(src, select={"FED103"}) == []


# ----------------------------------------------------------- FED104 --

def test_fed104_print_in_pallas_kernel_fires():
    src = """
        import jax.experimental.pallas as pl
        def kernel(x_ref, o_ref):
            print("traced once")
            o_ref[...] = x_ref[...]
        def call(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
    """
    fs = _ast(src, select={"FED104"})
    assert [f.rule for f in fs] == ["FED104"]
    assert "'print'" in fs[0].message


def test_fed104_ref_store_from_nested_loop_body_is_clean():
    # regression: a fori step that writes the enclosing kernel's output
    # ref is the kernel write idiom, not a closure mutation
    src = """
        import jax
        import jax.experimental.pallas as pl
        def kernel(x_ref, o_ref):
            def step(t, acc):
                o_ref[t] = acc
                return acc + x_ref[t]
            jax.lax.fori_loop(0, 4, step, 0.0)
        def call(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
    """
    assert _ast(src, select={"FED103", "FED104"}) == []


# ----------------------------------------------------- FED105/FED106 --

def test_fed105_bare_except_fires_and_typed_is_clean():
    assert [f.rule for f in _ast("try:\n    pass\nexcept:\n    pass\n",
                                 select={"FED105"})] == ["FED105"]
    assert _ast("try:\n    pass\nexcept ValueError:\n    raise\n",
                select={"FED105"}) == []


def test_fed106_swallow_in_checkpoint_path_fires():
    src = "try:\n    pass\nexcept OSError:\n    pass\n"
    fs = _ast(src, path="src/repro/checkpoint/io.py", select={"FED106"})
    assert [f.rule for f in fs] == ["FED106"]
    # same code outside the checkpoint/prefetcher scope: out of scope
    assert _ast(src, path="src/repro/core/round.py",
                select={"FED106"}) == []


def test_fed106_handled_exception_is_clean():
    src = ("try:\n    pass\nexcept OSError as e:\n"
           "    raise RuntimeError('ckpt') from e\n")
    assert _ast(src, path="src/repro/checkpoint/io.py",
                select={"FED106"}) == []


# ------------------------------------------------- FED100/suppression --

def test_suppression_without_justification_emits_fed100():
    src = "try:\n    pass\nexcept:  # fedlint: disable=FED105\n    pass\n"
    fs = run_file("fixture/mod.py", src, None)
    assert [f.rule for f in fs if not f.suppressed] == ["FED100"]
    supp = [f for f in fs if f.suppressed]
    assert [f.rule for f in supp] == ["FED105"]


def test_justified_suppression_is_silent():
    src = ("try:\n    pass\n"
           "except:  # fedlint: disable=FED105 — fixture: wants everything\n"
           "    pass\n")
    fs = run_file("fixture/mod.py", src, None)
    assert [f.rule for f in fs if not f.suppressed] == []
    assert fs[0].justification == "fixture: wants everything"


def test_standalone_suppression_governs_next_line():
    src = ("try:\n    pass\n"
           "# fedlint: disable=FED105 — fixture: next-line form\n"
           "except:\n    pass\n")
    fs = run_file("fixture/mod.py", src, None)
    assert [f.rule for f in fs if not f.suppressed] == []


# ------------------------------------------------------- layer 2 (jaxpr) --

def test_fed201_real_chunkrunner_lowering_aliases_the_carry():
    """The acceptance check: the loop ChunkRunner actually jits must
    alias every donated params leaf in its lowering."""
    from repro.exec.engine import ChunkRunner
    fl = jr._tiny_fl(algorithm="ama")
    h = jr.TraceHarness(fl)
    runner = ChunkRunner(h.model, fl, h.strategy)
    txt = runner._train_loop().lower(*h.loop_args()).as_text()
    n_params = len(jax.tree.leaves(h.state["params"]))
    assert txt.count("tf.aliasing_output") >= n_params
    # and the rule agrees
    assert jr.check_donation_aliasing([("ama", fl)]) == []


def test_fed201_fires_when_donation_is_dropped():
    fl = jr._tiny_fl(algorithm="ama")
    fs = jr.check_donation_aliasing([("ama", fl)], donate=False)
    assert [f.rule for f in fs] == ["FED201"]
    assert "aliases 0 buffers" in fs[0].message


def test_fed202_debug_print_in_scan_fires_clean_scan_passes():
    def dirty(c, x):
        jax.debug.print("c={c}", c=c)
        return c + x, x

    def clean(c, x):
        return c + x, x

    mk = lambda body: jax.make_jaxpr(
        lambda c, xs: jax.lax.scan(body, c, xs))(0.0, jnp.zeros(3))
    fs = jr.check_scan_effects([("fx", None)],
                               jaxpr_fn=lambda l, f: mk(dirty))
    assert fs and all(f.rule == "FED202" for f in fs)
    assert jr.check_scan_effects([("fx", None)],
                                 jaxpr_fn=lambda l, f: mk(clean)) == []


def test_fed203_carry_shape_and_structure_drift_fire():
    fl = jr._tiny_fl(algorithm="ama")
    sds = jax.ShapeDtypeStruct
    in_s = {"a": sds((2,), jnp.float32)}
    grown = {"a": sds((3,), jnp.float32)}
    restructured = {"a": sds((2,), jnp.float32), "b": sds((), jnp.int32)}
    fire = jr.check_carry_stability(
        [("fx", fl)], step_fn=lambda h: (grown, in_s))
    assert [f.rule for f in fire] == ["FED203"]
    fire2 = jr.check_carry_stability(
        [("fx", fl)], step_fn=lambda h: (restructured, in_s))
    assert [f.rule for f in fire2] == ["FED203"]
    assert jr.check_carry_stability(
        [("fx", fl)], step_fn=lambda h: (in_s, in_s)) == []


def _fake_ref(**overrides):
    from repro.kernels import ref as real
    ns = types.SimpleNamespace(__name__="fake_ref")
    for n in dir(real):
        if not n.startswith("_"):
            setattr(ns, n, getattr(real, n))
    for k, v in overrides.items():
        if v is None:
            delattr(ns, k)
        else:
            setattr(ns, k, v)
    return ns


def test_fed204_real_kernels_have_matching_oracles():
    assert jr.check_kernel_oracles() == []


def test_fed204_catches_a_renamed_oracle():
    fs = jr.check_kernel_oracles(None, _fake_ref(server_mix_math=None))
    assert [f.rule for f in fs] == ["FED204"]
    assert "server_mix_flat" in fs[0].message


def test_fed204_catches_a_signature_mismatch():
    bad = _fake_ref(server_mix_math=lambda prev, stacked: None)
    fs = jr.check_kernel_oracles(None, bad)
    assert [f.rule for f in fs] == ["FED204"]
    assert "does not match" in fs[0].message


def test_config_matrix_covers_every_registered_strategy():
    from repro.core import strategies
    labels = {label.split("+")[0] for label, _ in jr.config_matrix()}
    classes = {strategies.get(n) for n in strategies.names()}
    assert len(jr.config_matrix()) >= len(classes)
    assert {"ama", "fedavg"} <= labels


# ----------------------------------------------------------- CLI gate --

def _cli(args, cwd=REPO):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run([sys.executable, "-m", "repro.analysis", *args],
                          capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_json_schema_and_exit_zero_on_clean_paths():
    p = _cli(["--json", os.path.join("src", "repro", "analysis")])
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.loads(p.stdout)
    assert doc["tool"] == "fedlint" and doc["schema_version"] == 1
    assert set(doc["summary"]) == {"total", "suppressed", "unsuppressed"}
    assert doc["summary"]["unsuppressed"] == 0


def test_cli_exits_nonzero_on_unsuppressed_findings(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    pass\nexcept:\n    pass\n")
    p = _cli(["--json", str(bad)])
    assert p.returncode == 1, p.stdout + p.stderr
    doc = json.loads(p.stdout)
    assert any(f["rule"] == "FED105" for f in doc["findings"])
    assert doc["summary"]["unsuppressed"] == 1


def test_cli_list_rules_names_both_layers():
    p = _cli(["--list-rules"])
    assert p.returncode == 0
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("FED")]
    assert len(lines) >= 8
    assert any("jaxpr" in ln for ln in lines)


def test_repo_ast_layer_is_clean():
    """The tree the CI gate lints has zero unsuppressed AST findings."""
    paths = [os.path.join(REPO, p) for p in ("src", "benchmarks", "scripts")]
    fs = run_paths([p for p in paths if os.path.isdir(p)])
    assert [f for f in fs if not f.suppressed] == [], [
        f.render() for f in fs if not f.suppressed]
