"""Minitron-8B's published block against the plain f32 reference
(``chipbench/reference/lm.py``, ``xsilo.py``) at a CPU size: d_model
256, 6 query heads of 64 (q width 384 != d_model), 2 KV heads, d_ff
512, vocab 512, 4 layers, 64 tokens, seeded weights with the norms
moved off their zero init so that they count.

Tolerances: in f32 both sides compute the same sums in another order,
so they agree to a few ulps of the largest value (logits, loss) and of
each gradient leaf's norm; 1e-5 leaves that room tenfold.
"""
import hashlib
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "chipbench" / "tests")]

import xsilo_tiny  # noqa: E402
from chipbench import compare_lm  # noqa: E402
from chipbench.gen import weights  # noqa: E402
from chipbench.reference import lm, xsilo  # noqa: E402
from repro.configs.base import FLConfig  # noqa: E402
from repro.configs.minitron_8b import CONFIG  # noqa: E402
from repro.configs.registry import get_arch  # noqa: E402
from repro.core.round import init_state, make_round_step  # noqa: E402
from repro.launch.mesh import engine_mesh  # noqa: E402
from repro.models.api import build_model  # noqa: E402

TINY = {**xsilo_tiny.TINY_LM, "fes_tail_layers": 2, "rotary_frac": 0.5,
        "rope_theta": 10_000.0}
#: f32 agreement of two orders of the same sums (module docstring)
F32_TOL = 1e-5


def _program_cfg(dtype="float32"):
    return CONFIG.with_(**{k: TINY[k] for k in (
        "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
        "vocab_size", "num_layers", "fes_tail_layers")},
        dtype=dtype, param_dtype="float32")


def _weights(seed=7):
    p = weights.make(lm.param_specs(TINY), seed)
    # LayerNorm1p starts at weight 0, bias 0: move them so they count
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(hash(jax.tree_util.keystr(path)) % 2**31),
            a.shape) if path[-1].key in ("g", "b") else a, p)


def _tokens(seed=1, shape=(2, 64)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              TINY["vocab_size"])


def test_config_is_the_published_block():
    c = CONFIG
    assert (c.num_layers, c.d_model, c.num_heads, c.head_dim,
            c.num_kv_heads, c.d_ff, c.vocab_size) == (
        32, 4096, 48, 128, 8, 16384, 256_000)
    assert c.num_heads * c.head_dim == 6144 != c.d_model
    assert (c.mlp_act, c.norm, c.rotary_dim, c.rope_theta) == (
        "relu2", "layernorm1p", 64, 10_000.0)
    assert (c.dtype, c.param_dtype, c.qkv_bias) == ("bfloat16", "float32",
                                                    False)
    params = jax.eval_shape(build_model(c).init, jax.random.PRNGKey(0))
    assert "w_gate" not in params["body"]["mlp"]
    assert params["lm_head"]["w"].shape == (4096, 256_000)
    assert params["embed"]["table"].shape == (256_000, 4096)


def test_benchmark_config_keeps_every_published_width():
    cell = json.loads((ROOT / "chipbench" / "configs"
                       / "minitron-8b-4l.json").read_text())
    c = get_arch(cell["arch"])
    for k in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
              "mlp_act", "norm", "rope_theta", "rotary_frac", "dtype",
              "param_dtype", "fes_tail_layers"):
        assert cell[k] == getattr(c, k), k
    assert cell["published"] == {"num_layers": c.num_layers,
                                 "vocab_size": c.vocab_size}
    assert set(cell["reduced"]) == set(cell["published"])
    assert cell["num_layers"] >= 4
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(jax.eval_shape(
        lambda: weights.make(lm.param_specs(cell), 0))))
    assert n == cell["params_per_silo"] == 1_033_969_664


def test_logits_loss_and_gradients_match_the_reference_in_f32():
    model = build_model(_program_cfg().with_(remat=False))
    p, toks = _weights(), _tokens()
    with jax.default_matmul_precision("highest"):
        z, _ = model.forward(p, {"tokens": toks})
        z_ref = jnp.stack([lm.logits(p, TINY, t) for t in toks])
        loss, g = jax.value_and_grad(model.loss)(p, {"tokens": toks})
        loss_ref, g_ref = jax.value_and_grad(
            lambda q: lm.loss(q, TINY, toks))(p)
    scale = float(jnp.max(jnp.abs(z_ref)))
    assert float(jnp.max(jnp.abs(z - z_ref))) <= F32_TOL * scale
    assert abs(float(loss) - float(loss_ref)) <= F32_TOL * float(loss_ref)
    for path, a, b in zip(jax.tree_util.tree_leaves_with_path(g),
                          jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        assert float(jnp.linalg.norm(a - b)) <= F32_TOL * float(
            jnp.linalg.norm(b)), jax.tree_util.keystr(path[0])


def _one_round(dtype: str, seed: int = 3):
    """One federated round of 4 silos through ``make_round_step``
    (client_reduce "force" on a 1-device mesh) and through the
    reference, from the same seed."""
    ctx = xsilo_tiny.xsilo_context(seed, seq=32)
    tr, cfg = ctx.traffic, {**ctx.config, **TINY}
    fl = FLConfig(**{**tr["fl"], "client_reduce": "force"},
                  seed=seed % 2**31)
    model = build_model(_program_cfg(dtype))
    p0 = jax.device_get(_weights(seed))
    from repro import env as env_mod
    sb = env_mod.resolve(fl).batch(0, 1)
    chosen, limited = xsilo.round_plan(tr, fl.seed, 0)
    np.testing.assert_array_equal(sb["selected"][0], chosen)
    np.testing.assert_array_equal(sb["limited"][0], limited)
    toks = xsilo.round_batch(tr, seed, 0, chosen)
    sched = {k: jnp.asarray(sb[k][0]) for k in
             ("limited", "delayed", "delays", "data_sizes")}
    state = init_state(model, fl, None, params=jax.tree.map(jnp.asarray,
                                                            p0))
    with jax.set_mesh(engine_mesh(fl.cohorts)):
        out, m = jax.jit(make_round_step(model, fl))(
            state, {"tokens": jnp.asarray(toks)}, sched)
    ref = xsilo.run(tr, cfg, seed, fl.seed, p0, 1, jax.devices()[:1])
    return p0, ref, {"loss": [float(m["loss"])],
                     "p1": jax.device_get(out["params"])}


def test_round_matches_the_reference_in_f32():
    p0, ref, got = _one_round("float32")
    assert abs(got["loss"][0] - ref["loss"][0]) <= F32_TOL * ref["loss"][0]
    # each side rounds its stepped f32 weights once a step: the updates
    # may differ by a few ulps of each weight, and by the gradients' own
    # f32 agreement beyond that
    steps = xsilo_tiny.xsilo_context().traffic["fl"]["local_steps"]
    for x0, (a, b, d) in zip(jax.tree.leaves(p0), compare_lm.leaf_norms(
            p0, got["p1"], ref["p1"])):
        ulps = float(np.linalg.norm(np.spacing(np.abs(x0)).ravel()))
        assert d <= 2 * steps * ulps + F32_TOL * b, (a, b, d, ulps)


def test_bf16_compute_round_is_within_the_cell_limits():
    p0, ref, got = _one_round("bfloat16")
    limits = xsilo_tiny.xsilo_context().limits
    n = compare_lm.numbers(p0, {**ref, "p2": ref["p1"]},
                           {**got, "p2": got["p1"]})
    assert all(n[k] <= limits[k] for k in limits), n


def test_a_bf16_carry_fails_body_update_diff_at_the_cells_lr():
    ctx = xsilo_tiny.xsilo_context(5, seq=32)
    tr, cfg = ctx.traffic, {**ctx.config, **TINY}
    assert tr["fl"]["lr"] == 0.001
    p0 = jax.device_get(_weights(5))
    ref = xsilo.run(tr, cfg, 5, 5, p0, 1, jax.devices()[:1])
    bad = xsilo.run(tr, cfg, 5, 5, p0, 1, jax.devices()[:1],
                    fault="bf16_carry")
    d = compare_lm.body_diff(p0, ref["p1"], bad["p1"])
    assert d > ctx.limits["body_update_diff"], d


def test_reference_forward_matches_transformers_nemotron():
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    c = TINY
    hf = transformers.NemotronConfig(
        vocab_size=c["vocab_size"], hidden_size=c["d_model"],
        intermediate_size=c["d_ff"], num_hidden_layers=c["num_layers"],
        num_attention_heads=c["num_heads"],
        num_key_value_heads=c["num_kv_heads"], head_dim=c["head_dim"],
        hidden_act="relu2", norm_eps=1e-5, rope_theta=c["rope_theta"],
        partial_rotary_factor=c["rotary_frac"], tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False, max_position_embeddings=128)
    net = transformers.NemotronForCausalLM(hf).eval()
    p = jax.device_get(_weights())
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    sd = {"model.embed_tokens.weight": t(p["embed"]["table"]),
          "model.norm.weight": t(p["final_norm"]["g"]),
          "model.norm.bias": t(p["final_norm"]["b"]),
          "lm_head.weight": t(p["lm_head"]["w"].T)}
    for i, lp in enumerate(lm.layers(p)):
        pre = f"model.layers.{i}."
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj")):
            sd[pre + f"self_attn.{theirs}.weight"] = t(lp["attn"][ours]["w"].T)
        sd[pre + "mlp.up_proj.weight"] = t(lp["mlp"]["w_in"]["w"].T)
        sd[pre + "mlp.down_proj.weight"] = t(lp["mlp"]["w_out"]["w"].T)
        for ours, theirs in (("ln1", "input_layernorm"),
                             ("ln2", "post_attention_layernorm")):
            sd[pre + f"{theirs}.weight"] = t(lp[ours]["g"])
            sd[pre + f"{theirs}.bias"] = t(lp[ours]["b"])
    missing, unexpected = net.load_state_dict(sd, strict=False)
    assert not unexpected and not [k for k in missing
                                   if "rotary" not in k], missing
    toks = np.asarray(_tokens(shape=(1, 64)))
    with torch.no_grad():
        z_hf = net(torch.tensor(toks, dtype=torch.long)).logits[0].numpy()
    with jax.default_matmul_precision("highest"):
        z = np.asarray(lm.logits(p, c, jnp.asarray(toks[0])))
    # f32 on both sides: agreement to rounding of the largest logit
    assert np.max(np.abs(z - z_hf)) <= F32_TOL * np.max(np.abs(z_hf))


#: sha256 of the lowered text of the paper CNN's round program (one
#: round, 3 clients of 2 steps of 8 images, f32): its params are f32,
#: so keeping masters in another dtype must leave it as it was before
#: master weights existed. A deliberate change to that program updates
#: this digest.
CNN_ROUND_SHA256 = ("b295ea408e6a4b55c3a118b4679be893"
                    "bb0be499ea30e2422f4b5075069b7847")


def _cnn_round_text() -> str:
    cfg = get_arch("paper-cnn")
    model = build_model(cfg)
    fl = FLConfig(num_clients=3, clients_per_round=3, cohorts=3)
    state = jax.eval_shape(lambda: init_state(model, fl,
                                              jax.random.PRNGKey(0)))
    batch = {"image": jax.ShapeDtypeStruct((3, 2, 8, 28, 28, 1),
                                           jnp.float32),
             "label": jax.ShapeDtypeStruct((3, 2, 8), jnp.int32)}
    sched = {"limited": jax.ShapeDtypeStruct((3,), jnp.bool_),
             "delayed": jax.ShapeDtypeStruct((3,), jnp.bool_),
             "delays": jax.ShapeDtypeStruct((3,), jnp.int32),
             "data_sizes": jax.ShapeDtypeStruct((3,), jnp.float32)}
    return jax.jit(make_round_step(model, fl)).lower(
        state, batch, sched).as_text()


def test_paper_cnn_round_program_is_unchanged():
    text = _cnn_round_text()
    assert "bf16" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == CNN_ROUND_SHA256


#: sha256 of the lowered text of the cross-silo round with the client
#: axis pre-reduced (``client_reduce="force"``): the tiny Minitron block
#: in bf16 compute over f32 masters, 4 silos of 2 steps of 2 x 32
#: tokens, the cell's own AMA-FES traffic, on a one-device
#: ("client", "dsub", "model") mesh. It is the server path the
#: four-chip cell runs (``reduced_server_update``); a deliberate change
#: to that program updates this digest.
REDUCED_ROUND_SHA256 = ("c84d0dfc5b4415baeb6791e53f28eb28"
                        "b31fa11299caa3af61a78ef7d663831d")


def _reduced_round_lowered():
    tr = xsilo_tiny.xsilo_context(seq=32).traffic
    fl = FLConfig(**{**tr["fl"], "client_reduce": "force"})
    model = build_model(_program_cfg("bfloat16"))
    state = jax.eval_shape(lambda: init_state(model, fl,
                                              jax.random.PRNGKey(0)))
    C, steps = fl.cohorts, fl.local_steps
    batch = {"tokens": jax.ShapeDtypeStruct((C, steps, 2, 32), jnp.int32)}
    sched = {"limited": jax.ShapeDtypeStruct((C,), jnp.bool_),
             "delayed": jax.ShapeDtypeStruct((C,), jnp.bool_),
             "delays": jax.ShapeDtypeStruct((C,), jnp.int32),
             "data_sizes": jax.ShapeDtypeStruct((C,), jnp.float32)}
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                             ("client", "dsub", "model"))
    with jax.set_mesh(mesh):
        return jax.jit(make_round_step(model, fl)).lower(
            state, batch, sched)


def test_reduced_round_program_is_unchanged():
    lowered = _reduced_round_lowered()
    assert "client_reduce" in lowered.as_text(debug_info=True)
    text = lowered.as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == REDUCED_ROUND_SHA256


def test_paper_cnn_round_program_pools_without_window_ops():
    """Max-pooling lowers to elementwise ops (``models/cnn.py``
    ``max_pool_2x2``): no ``reduce_window`` forward, no
    ``select_and_scatter`` gradient, both slow generic paths on a TPU."""
    text = _cnn_round_text()
    assert "reduce_window" not in text
    assert "select_and_scatter" not in text


#: a convolution op of the lowered text whose result is a 5x5x1x... f32
#: tensor: conv1's filter gradient under the clients' vmap
CONV1_FILTER_GRAD = re.compile(
    r"stablehlo\.convolution.*-> tensor<5x5x1x\d+xf32>")
#: a precision of a dot or a convolution, as the lowered text names it
PRECISION = re.compile(r"\b(DEFAULT|HIGHEST|HIGH)\b")


def test_paper_cnn_round_program_takes_conv1_filter_grad_as_a_dot():
    """conv1's filter gradient is a batched dot of the images' row
    slices (``models/cnn.py`` ``conv_valid``), not the grouped
    convolution that autodiff of the vmapped convolution gives: the
    round keeps the two forward convolutions and conv2's two gradients,
    all at the default precision, as before."""
    text = _cnn_round_text()
    assert not CONV1_FILTER_GRAD.search(text)
    assert text.count("stablehlo.convolution") == 4
    assert "bf16" not in text
    assert set(PRECISION.findall(text)) == {"DEFAULT"}
