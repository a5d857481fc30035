"""Serving plane: chunked-prefill bit-identity, paged-pool parity,
engine token equality, scheduler invariants, checkpoint round-trip and
serve telemetry rows.

The load-bearing contract is BIT-identity: the jitted chunked prefill
and the paged decode/prefill paths must produce bitwise the same logits
AND cache contents as the seed per-token dense loop, so switching
engines can never change served tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import restore_params, save
from repro.configs.base import reduced
from repro.configs.registry import ARCHS
from repro.models import attention as attn
from repro.models.api import build_model
from repro.obs.log import MetricsLogger, validate_rows
from repro.serve import (KVPool, LoopEngine, PagedEngine, Request,
                         Scheduler, latency_percentiles)


# --------------------------------------------------------------- fixtures
def _build(cfg):
    """The model and its served weights: the compute-dtype copy of the
    masters ``init`` makes (what the engines serve)."""
    model = build_model(cfg)
    params = model.compute_copy(model.init(jax.random.PRNGKey(0)))
    return model, params


@pytest.fixture(scope="module")
def dense():
    return _build(reduced(ARCHS["minitron-8b"]))


@pytest.fixture(scope="module")
def swa8():
    # window 8 < prompt lengths below -> the ring WRAPS during prefill
    return _build(reduced(ARCHS["minitron-8b"]).with_(sliding_window=8))


@pytest.fixture(scope="module")
def encdec():
    return _build(reduced(ARCHS["whisper-medium"]))


def _prompts(cfg, B, P, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(1, cfg.vocab_size, (B, P)), jnp.int32)


def _init_cache(model, params, B, max_len):
    if model.cfg.family == "audio":
        fe = jnp.zeros((B, model.cfg.encoder_seq, model.cfg.d_model),
                       jnp.dtype(model.cfg.dtype))
        return model.init_decode_cache(params, fe, max_len)
    return model.init_decode_cache(params, B, max_len)


def _per_token(model, params, prompts, max_len):
    B, P = prompts.shape
    cache = _init_cache(model, params, B, max_len)
    step = jax.jit(model.decode_step)
    outs = []
    for t in range(P):
        lg, cache = step(params, prompts[:, t],
                         jnp.full((B,), t, jnp.int32), cache)
        outs.append(lg)
    return jnp.stack(outs, 1), cache


def _chunked(model, params, prompts, max_len, c, pad_fill=0):
    B, P = prompts.shape
    cache = _init_cache(model, params, B, max_len)
    pf = jax.jit(model.prefill)
    lgs = []
    for t0 in range(0, P, c):
        n = min(c, P - t0)
        toks = np.full((B, c), pad_fill, np.int32)
        poss = np.full((B, c), attn.PAD_POS, np.int32)
        toks[:, :n] = np.asarray(prompts[:, t0:t0 + n])
        poss[:, :n] = np.arange(t0, t0 + n)
        lg, cache = pf(params, jnp.asarray(toks), jnp.asarray(poss), cache)
        lgs.append(lg[:, :n])
    return jnp.concatenate(lgs, 1), cache


def _trees_equal(a, b):
    return all(bool(jnp.all(x == y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ------------------------------------------- chunked prefill bit-identity
@pytest.mark.parametrize("fix,c", [("dense", 4), ("swa8", 5),
                                   ("encdec", 4)])
def test_prefill_bit_identical(fix, c, request):
    """Chunked prefill == per-token decode, bitwise, logits AND cache —
    incl. a ragged final chunk (P % c != 0) whose PAD tail must be
    inert, and (swa8) prompts that wrap the sliding-window ring."""
    model, params = request.getfixturevalue(fix)
    B, P, max_len = 2, 11, 20
    prompts = _prompts(model.cfg, B, P)
    ref_lg, ref_c = _per_token(model, params, prompts, max_len)
    blk_lg, blk_c = _chunked(model, params, prompts, max_len, c)
    assert bool(jnp.all(ref_lg == blk_lg))
    assert _trees_equal(ref_c, blk_c)


def test_prefill_pad_garbage_inert(dense):
    """PAD positions are fully predicated: garbage token ids under PAD
    must not perturb logits or cache by a single bit."""
    model, params = dense
    prompts = _prompts(model.cfg, 2, 7)          # 7 % 3 != 0 -> PAD tail
    lg0, c0 = _chunked(model, params, prompts, 16, 3, pad_fill=0)
    lg1, c1 = _chunked(model, params, prompts, 16, 3,
                       pad_fill=model.cfg.vocab_size - 1)
    assert bool(jnp.all(lg0 == lg1))
    assert _trees_equal(c0, c1)


# ------------------------------------------------- paged vs dense parity
@pytest.mark.parametrize("fix", ["dense", "swa8"])
def test_paged_bit_identical_to_dense(fix, request):
    """Paged decode AND paged chunked prefill == the dense cache path,
    bitwise, when the block table covers the same ring (mb*bs == L)."""
    model, params = request.getfixturevalue(fix)
    cfg = model.cfg
    B, P, max_len, bs = 2, 12, 24, 4
    L = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    mb = L // bs
    assert mb * bs == L
    prompts = _prompts(cfg, B, P)
    ref, _ = _per_token(model, params, prompts, max_len)

    nb = 1 + B * mb
    table = jnp.asarray(
        np.arange(1, nb, dtype=np.int32).reshape(B, mb))
    lw = jnp.full((B,), L, jnp.int32)

    pool = model.init_paged_pool(nb, bs)
    pstep = jax.jit(model.decode_step_paged)
    outs = []
    for t in range(P):
        lg, pool = pstep(params, prompts[:, t],
                         jnp.full((B,), t, jnp.int32), pool, table, lw)
        outs.append(lg)
    assert bool(jnp.all(ref == jnp.stack(outs, 1)))

    pool2 = model.init_paged_pool(nb, bs)
    ppf = jax.jit(model.prefill_paged)
    c = 5
    lgs = []
    for t0 in range(0, P, c):
        n = min(c, P - t0)
        toks = np.zeros((B, c), np.int32)
        poss = np.full((B, c), attn.PAD_POS, np.int32)
        toks[:, :n] = np.asarray(prompts[:, t0:t0 + n])
        poss[:, :n] = np.arange(t0, t0 + n)
        lg, pool2 = ppf(params, jnp.asarray(toks), jnp.asarray(poss),
                        pool2, table, lw)
        lgs.append(lg[:, :n])
    assert bool(jnp.all(ref == jnp.concatenate(lgs, 1)))
    assert _trees_equal(pool, pool2)     # same blocks written, same bits


# ------------------------------------------------- engines: e2e equality
def _mkreqs(vocab, lens, max_new, seed=1):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, max_new=max_new,
                    prompt=rng.randint(1, vocab, (ln,)).tolist())
            for i, ln in enumerate(lens)]


def test_engines_serve_identical_tokens(dense):
    """loop(per-token) == loop(chunked prefill) == paged continuous
    batching, token for token — with more requests than slots, so the
    paged run exercises slot reuse and block recycling."""
    model, params = dense
    vocab = model.cfg.vocab_size
    lens, max_new = [5, 11, 8, 14], 6
    ra = LoopEngine(model, params).run(_mkreqs(vocab, lens, max_new))
    rb = LoopEngine(model, params, prefill_chunk=4).run(
        _mkreqs(vocab, lens, max_new))
    eng = PagedEngine(model, params, max_slots=2, block_size=4,
                      max_batch_tokens=64, prefill_chunk=4)
    rc = eng.run(_mkreqs(vocab, lens, max_new))
    for x, y, z in zip(ra, rb, rc):
        assert x["tokens"] == y["tokens"] == z["tokens"]
        assert x["new_tokens"] == max_new
    # results come back in submission order regardless of finish order
    assert [r["id"] for r in rc] == list(range(len(lens)))


def test_loop_engine_pads_never_enter_cache(dense):
    """Variable-length prompts in the lockstep loop: each row's tokens
    must match a solo run of that row (the seed fed row 0's layout to
    every row, corrupting shorter prompts)."""
    model, params = dense
    vocab = model.cfg.vocab_size
    reqs = _mkreqs(vocab, [4, 9], 5)
    both = LoopEngine(model, params).run(
        _mkreqs(vocab, [4, 9], 5))
    for i, r in enumerate(reqs):
        solo = LoopEngine(model, params).run(
            [Request(rid=0, prompt=list(r.prompt), max_new=5)])
        assert solo[0]["tokens"] == both[i]["tokens"]


def test_paged_engine_checkpoint_restore_serves_identically(dense,
                                                            tmp_path):
    """Params through a save/restore round-trip serve bit-identical
    tokens — serving a restored federated model is the product path."""
    model, params = dense
    path = str(tmp_path / "params.npz")
    save(path, params)
    back = restore_params(path, params)
    vocab = model.cfg.vocab_size
    r0 = PagedEngine(model, params, max_slots=2, block_size=4,
                     prefill_chunk=4).run(_mkreqs(vocab, [6, 13], 5))
    r1 = PagedEngine(model, back, max_slots=2, block_size=4,
                     prefill_chunk=4).run(_mkreqs(vocab, [6, 13], 5))
    assert [r["tokens"] for r in r0] == [r["tokens"] for r in r1]


def test_loop_engine_serves_recurrent_family():
    """ssm family has no KV ring -> LoopEngine per-token still serves
    it (and PagedEngine refuses it loudly)."""
    model, params = _build(reduced(ARCHS["rwkv6-3b"]))
    out = LoopEngine(model, params).run(
        _mkreqs(model.cfg.vocab_size, [4, 7], 3))
    assert all(r["new_tokens"] == 3 for r in out)
    with pytest.raises(ValueError, match="no paged serving path"):
        PagedEngine(model, params)


# ------------------------------------------------- scheduler invariants
def test_scheduler_fifo_no_starvation_and_budget():
    # footprints (prompt + max_new): rid0=10, rid1=12, rid2=6, rid3=4
    s = Scheduler(max_batch_tokens=20)
    for i, (p, n) in enumerate([(6, 4), (8, 4), (4, 2), (2, 2)]):
        s.submit(Request(rid=i, prompt=[1] * p, max_new=n))

    def drain():
        out = []
        while True:
            r = s.try_admit(can_place=lambda r: True)
            if r is None:
                return out
            out.append(r)

    # rid0 fits (10 <= 20); head rid1 would hit 22 > 20 -> blocked, and
    # FIFO means rid2 (which WOULD fit) must not jump the queue
    assert [r.rid for r in drain()] == [0]
    s.release(s.inflight[0])
    # rid1 (12), then rid2 (12+6=18 <= 20); rid3 would hit 22 -> blocked
    assert [r.rid for r in drain()] == [1, 2]
    s.release(s.inflight[2])
    assert [r.rid for r in drain()] == [3]
    assert s.admitted_order == s.submitted_order    # nobody overtaken
    assert s.peak_inflight_tokens <= 20


def test_scheduler_oversized_head_admitted_when_idle():
    """A request larger than the whole budget must still run (when
    nothing is in flight) rather than wedge the queue forever."""
    s = Scheduler(max_batch_tokens=8)
    s.submit(Request(rid=0, prompt=[1] * 20, max_new=4))
    r = s.try_admit(can_place=lambda r: True)
    assert r is not None and r.rid == 0


def test_paged_engine_scheduler_and_pool_invariants(dense):
    """After a full run: FIFO admission order, every slot reused, all
    blocks back on the free list (conservation), budget respected."""
    model, params = dense
    vocab = model.cfg.vocab_size
    eng = PagedEngine(model, params, max_slots=2, block_size=4,
                      max_batch_tokens=64, prefill_chunk=4)
    reqs = _mkreqs(vocab, [5, 11, 8, 14, 6], 4)
    out = eng.run(reqs)
    assert all(r["new_tokens"] == 4 for r in out)
    sched, kv = eng.scheduler, eng.kv
    assert sched.admitted_order == sched.submitted_order
    assert sched.peak_inflight_tokens <= 64
    assert sched.pending == 0 and not sched.inflight
    # 5 requests through 2 slots -> at least one slot served >= 3
    assert sum(len(v) for v in sched.slot_history.values()) == len(reqs)
    assert max(len(v) for v in sched.slot_history.values()) >= 3
    # block conservation: everything freed back (block 0 stays reserved)
    assert kv.free_blocks == kv.num_blocks - 1
    assert kv.used_blocks == 0


def test_paged_engine_rejects_unservable_request(dense):
    """A request whose ring cannot fit in the pool fails loudly instead
    of deadlocking the admission loop."""
    model, params = dense
    eng = PagedEngine(model, params, max_slots=1, block_size=4,
                      num_blocks=3, prefill_chunk=4)   # 2 usable blocks
    with pytest.raises(RuntimeError, match="blocks"):
        eng.run(_mkreqs(model.cfg.vocab_size, [20], 4))


def test_kv_pool_alloc_free_roundtrip(dense):
    model, _ = dense
    kv = KVPool(model, num_blocks=5, block_size=4)
    assert kv.free_blocks == 4                  # block 0 reserved
    got = kv.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert kv.used_blocks == 3 and not kv.can_alloc(2)
    kv.free(got)
    assert kv.free_blocks == 4
    # freeing resets the pos entries -> gathered views see "unwritten"
    for g in kv.pool.values():
        assert bool(jnp.all(g["pos"][:, got] == -1))


# ----------------------------------------------------- serve telemetry
def test_metrics_logger_serve_rows_validate(dense):
    model, params = dense
    eng = LoopEngine(model, params)
    results = eng.run(_mkreqs(model.cfg.vocab_size, [4, 7], 3))
    log = MetricsLogger(path=None)
    log.header(extra={"serve": {"engine": "loop"}})
    for r in results:
        log.serve(r)
    log.serve_summary(eng.last_summary)
    assert validate_rows(log.rows) == []
    serve_rows = [r for r in log.rows if r["kind"] == "serve"]
    assert len(serve_rows) == 2
    assert all("tokens" not in r for r in serve_rows)   # ids stay private
    assert [r["new_tokens"] for r in serve_rows] == [3, 3]


def test_latency_percentiles_shape():
    p = latency_percentiles([0.010, 0.020, 0.100])
    assert set(p) == {"p50_ms", "p95_ms", "p99_ms"}
    assert p["p50_ms"] == 20.0 and p["p95_ms"] <= p["p99_ms"]
    assert latency_percentiles([])["p50_ms"] is None
