"""Fused server-plane kernels: the COMPLETE server update in one HBM pass.

The per-round server hot loop — staleness/participation weight
computation from the schedule, weighted accumulation of the stacked
(K, N) client params, the AMA mix (with the async ring buffer where the
environment has delays), and the optional FedOpt server-Adam moment
update — is purely HBM-bandwidth-bound at LLM scale. Before this module
each stage was a separate jnp pass materialising (N,)/(K, N)/(Q, N)
intermediates; here each round is ONE ``pl.pallas_call`` over a 1-D grid
of parameter tiles:

  * ``server_mix_flat``       — sync plane (ama / fedavg / fedprox):
        streams K+1 rows in, 1 out;
  * ``server_mix_delta_flat`` — the same plane on compressed client
        deltas (int8 / bf16 rows upcast inside the tile; the top-k
        uplink is densified into it by ``server_mix_compressed_tree``);
  * ``server_async_flat``     — async plane (async_ama, Eqs. 6-11):
        streams K+Q+1 rows in, Q+1 out; ring-buffer enqueue, slot pop
        and the alpha/beta/gamma mix fused;
  * ``server_adam_flat``      — FedOpt server-Adam:
        streams K+3 rows in, 3 out; pseudo-gradient, moments and the
        model step fused.

Each wrapper computes the plane's O(K·Q) scalar half
(``kernels/ref.py: server_*_coefs``) in XLA and passes it to the kernel
as one f32 vector in SMEM; the kernel body runs the plane's O(N) half
(``ref.mix_apply`` / ``async_apply`` / ``adam_apply``) on its tile —
the SAME functions the jnp oracle runs on whole arrays, so interpret
mode matches the oracle to within 1-2 ulp and compiled TPU mode is
allclose. The ``server_*_tree`` drivers flatten a whole param pytree to
one vector per dtype group (bf16 and f32 leaves keep their dtypes), so
the engine dispatches ONE fused pass per round per dtype group.

Dispatch policy (``_route`` / ``fl.server_plane``): the Pallas
pallas_call is the TPU lowering; OFF-TPU the "fused" impl runs the
jitted flat oracle instead — XLA CPU fuses the whole flat op sequence
into one pass, while the Pallas INTERPRETER is a pure emulation layer
that is orders of magnitude slower and exists only to validate the
kernel body (impl="interpret", CI parity tests).

Layout and block size: a flat (N,) vector is viewed as (R, 128) lane
rows (zero-padded when 128 does not divide N) and a stacked (K, N)
operand as (K, R, 128), so the client and ring axes are leading block
dims that need no sublane padding. A grid step streams ``block_rows``
lane rows of every operand. ``_block_rows`` derives that count from
``VMEM_BUDGET``: for each operand streamed in or out, its rows times
128 lanes times its itemsize, times 2 for the pipeline's double
buffering, plus the f32 temporaries the body keeps live (accumulators,
upcast rows, the Q ring rows of the async plane); the count is rounded
down to the largest sublane tile among the operands (8 rows f32, 16
bf16, 32 int8), and a block holding every row is the whole array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

LANES = 128
#: bytes of VMEM the double-buffered tiles and live temporaries of one
#: grid step may take: half of the 16 MiB scoped-VMEM default of a v5e
#: TensorCore, leaving the rest to Mosaic's own scratch, so no kernel
#: raises the scoped limit
VMEM_BUDGET = 8 * 1024 * 1024

__all__ = ["server_mix_flat", "server_async_flat", "server_adam_flat",
           "server_mix_delta_flat", "server_mix_tree", "server_async_tree",
           "server_adam_tree", "server_mix_compressed_tree", "mix_coefs",
           "VMEM_BUDGET"]


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# The "ref" impl runs the oracle math under jit so XLA applies the same
# multiply-add contraction it applies to the interpret-mode kernel body —
# that (plus the shared op sequence) keeps ref == interpret within
# 1-2 ulp even when called eagerly (contraction is shape-dependent, so
# strict bit-equality across different blockings is not guaranteed).
_ref_mix = jax.jit(ref.server_mix_math)
_ref_async = jax.jit(ref.server_async_math)
_ref_adam = jax.jit(ref.server_adam_math)


def _route(impl: str) -> tuple[bool, bool]:
    """Resolve an impl name to (use_pallas_kernel, interpret_flag):

      "fused"     — the production path: pallas_call on TPU, the jitted
                    flat oracle off-TPU (one XLA fusion; the Pallas
                    INTERPRETER is emulation, not a perf path);
      "ref"       — always the jitted flat oracle;
      "interpret" — force the Pallas kernel through the interpreter
                    (kernel-body validation in CI, 1-2 ulp vs "ref").
    """
    if impl == "interpret":
        return True, True
    if impl == "fused":
        return not _interpret_default(), False
    if impl != "ref":
        raise ValueError(f"unknown server-plane impl {impl!r}")
    return False, False


def mix_coefs(fl, t, *, adaptive: bool = True):
    """(4,) f32 = [alpha0, eta, alpha_cap, t] for ``server_mix_*``.
    ``adaptive=False`` zeroes the schedule (fedavg/fedprox: alpha == 0)."""
    tf = jnp.asarray(t, jnp.float32)
    if not adaptive:
        z = jnp.float32(0.0)
        return jnp.stack([z, z, z, tf])
    return jnp.stack([jnp.float32(fl.alpha0), jnp.float32(fl.eta),
                      jnp.float32(fl.alpha_cap), tf])


# ---------------------------------------------------------------------------
# tiling: (N,) -> (R, 128) lane rows, block rows from the VMEM budget
# ---------------------------------------------------------------------------

def _sublanes(dtype) -> int:
    """Rows of one (sublane, 128) VMEM tile: 8 f32, 16 bf16, 32 int8."""
    return 32 // jnp.dtype(dtype).itemsize


def _block_rows(R: int, streams, temps: int, block: int | None) -> int:
    """Lane rows per grid step (see the module docstring).

    ``streams``: (rows, dtype) for every operand streamed in or out per
    lane row — (K, stacked.dtype) for the client rows. ``temps``: f32
    lane rows the body keeps live. ``block`` (elements, a multiple of
    128) overrides the budget, for tests that exercise many tiles on a
    small N."""
    if block is not None:
        br = max(1, block // LANES)
    else:
        tile = max(_sublanes(d) for _, d in streams)
        per_row = LANES * (2 * sum(n * jnp.dtype(d).itemsize
                                   for n, d in streams) + 4 * temps)
        br = max(tile, VMEM_BUDGET // per_row // tile * tile)
    return min(br, R)


def _lanes(x):
    """(..., N) -> (..., R, 128), zero-padding N to a multiple of 128."""
    pad = (-x.shape[-1]) % LANES
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (-1, LANES))


def _unlanes(x, N: int):
    """Inverse of ``_lanes``."""
    x = x.reshape(x.shape[:-2] + (-1,))
    return x[..., :N] if x.shape[-1] != N else x


def _rows(br: int, lead: int = 0):
    """BlockSpec of ``br`` lane rows (behind ``lead`` whole rows)."""
    if lead:
        return pl.BlockSpec((lead, br, LANES), lambda i: (0, i, 0))
    return pl.BlockSpec((br, LANES), lambda i: (i, 0))


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _pallas(kernel, R, br, in_specs, out_specs, out_shape, interpret):
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(R, br),), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret)


# ---------------------------------------------------------------------------
# kernel bodies: the shared O(N) half on one tile; coefficients in SMEM
# ---------------------------------------------------------------------------

def _mix_kernel(c_ref, prev_ref, rows_ref, out_ref):
    out_ref[...] = ref.mix_apply(prev_ref[...], rows_ref, c_ref)


def _async_kernel(c_ref, prev_ref, rows_ref, qrows_ref, out_ref,
                  qout_ref):
    out, ring = ref.async_apply(prev_ref[...], rows_ref, qrows_ref, c_ref)
    out_ref[...] = out
    for q, row in enumerate(ring):
        qout_ref[q] = row


def _adam_kernel(c_ref, prev_ref, rows_ref, m_ref, v_ref, out_ref,
                 m_out_ref, v_out_ref):
    out, new_m, new_v = ref.adam_apply(prev_ref[...], rows_ref, m_ref[...],
                                       v_ref[...], c_ref)
    out_ref[...] = out
    m_out_ref[...] = new_m
    v_out_ref[...] = new_v


# ---------------------------------------------------------------------------
# flat wrappers: coefficients in XLA, one pallas_call over lane rows
# ---------------------------------------------------------------------------

def _mix_pass(prev, rows, c, block, interpret):
    """out = prev*c[0] + sum_k rows[k]*c[k+1] as one kernel pass."""
    N, K = prev.shape[0], rows.shape[0]
    p, r = _lanes(prev), _lanes(rows)
    R = p.shape[0]
    br = _block_rows(R, [(2, prev.dtype), (K, rows.dtype)], 3, block)
    out = _pallas(_mix_kernel, R, br, [_SMEM, _rows(br), _rows(br, K)],
                  _rows(br), jax.ShapeDtypeStruct(p.shape, p.dtype),
                  interpret)(c, p, r)
    return _unlanes(out, N)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def server_mix_flat(prev, stacked, sizes, keep, coefs, *,
                    block: int | None = None, interpret: bool = False):
    """prev: (N,); stacked: (K, N); sizes/keep: (K,) f32; coefs: (4,)."""
    return _mix_pass(prev, stacked, ref.server_mix_coefs(sizes, keep, coefs),
                     block, interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def server_mix_delta_flat(prev, dstacked, rowscale, sizes, keep, coefs, *,
                          block: int | None = None,
                          interpret: bool = False):
    """Compressed-uplink sync plane: prev (N,); dstacked (K, N) quantized
    deltas (int8 / bf16 / f32); rowscale (K,) f32 dequantization scales;
    sizes/keep (K,) f32; coefs (4,). Dequantize-accumulate fused: the
    int8/bf16 rows upcast INSIDE the kernel tile, so the server's HBM
    pass streams the compressed bytes, not a dense f32 copy."""
    return _mix_pass(prev, dstacked,
                     ref.server_mix_delta_coefs(rowscale, sizes, keep,
                                                coefs),
                     block, interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def server_async_flat(prev, stacked, qsum, qgamma, sizes, delayed, delays,
                      tq, hyp, *, block: int | None = None,
                      interpret: bool = False):
    """prev: (N,); stacked: (K, N); qsum: (Q, N) f32; qgamma: (Q,) f32;
    sizes/delayed: (K,) f32; delays: (K,) i32; tq: (2,) i32 = [t, t % Q];
    hyp: (4,) f32 = [alpha0, eta, alpha_cap, staleness_b].
    Returns (out (N,), new_qsum (Q, N) f32, new_qgamma (Q,) f32)."""
    N, K, Q = prev.shape[0], stacked.shape[0], qgamma.shape[0]
    c, new_qgamma = ref.server_async_coefs(qgamma, sizes, delayed, delays,
                                           tq, hyp)
    p, r, q = _lanes(prev), _lanes(stacked), _lanes(qsum)
    R = p.shape[0]
    br = _block_rows(R, [(2, prev.dtype), (K, stacked.dtype),
                         (2 * Q, jnp.float32)], Q + 3, block)
    out, new_q = _pallas(
        _async_kernel, R, br,
        [_SMEM, _rows(br), _rows(br, K), _rows(br, Q)],
        (_rows(br), _rows(br, Q)),
        (jax.ShapeDtypeStruct(p.shape, p.dtype),
         jax.ShapeDtypeStruct(q.shape, jnp.float32)),
        interpret)(c, p, r, q)
    return _unlanes(out, N), _unlanes(new_q, N), new_qgamma


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def server_adam_flat(prev, stacked, m, v, sizes, keep, scalars, *,
                     block: int | None = None, interpret: bool = False):
    """prev: (N,); stacked: (K, N); m/v: (N,) f32; sizes/keep: (K,) f32;
    scalars: (5,) f32 = [b1, b2, lr, tau, step] (step pre-incremented).
    Returns (out (N,), new_m (N,) f32, new_v (N,) f32)."""
    N, K = prev.shape[0], stacked.shape[0]
    c = ref.server_adam_coefs(sizes, keep, scalars)
    p, r, lm, lv = _lanes(prev), _lanes(stacked), _lanes(m), _lanes(v)
    R = p.shape[0]
    br = _block_rows(R, [(2, prev.dtype), (K, stacked.dtype),
                         (4, jnp.float32)], 7, block)
    f32_rows = jax.ShapeDtypeStruct(p.shape, jnp.float32)
    out, new_m, new_v = _pallas(
        _adam_kernel, R, br,
        [_SMEM, _rows(br), _rows(br, K), _rows(br), _rows(br)],
        (_rows(br), _rows(br), _rows(br)),
        (jax.ShapeDtypeStruct(p.shape, p.dtype), f32_rows, f32_rows),
        interpret)(c, p, r, lm, lv)
    return _unlanes(out, N), _unlanes(new_m, N), _unlanes(new_v, N)


# ---------------------------------------------------------------------------
# tree drivers: whole param pytree -> one flat vector per dtype group ->
# one kernel call per round per group
# ---------------------------------------------------------------------------

def _dtype_groups(leaves):
    """Leaf indices grouped by dtype, insertion-ordered (usually 1 group)."""
    groups: dict = {}
    for i, x in enumerate(leaves):
        groups.setdefault(jnp.asarray(x).dtype, []).append(i)
    return groups


def _cat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _split_back(flat, leaves_like, idxs, out_leaves):
    lead = 1
    for d in flat.shape[:-1]:       # leading (K,)/(Q,) axes, if any
        lead *= d
    off = 0
    for i in idxs:
        n = leaves_like[i].size // lead
        out_leaves[i] = flat[..., off:off + n].reshape(leaves_like[i].shape)
        off += n


def _co_leaves(tree, treedef):
    leaves, td = jax.tree.flatten(tree)
    assert td == treedef, "co-tree structure mismatch"
    return leaves


def server_mix_tree(prev, stacked, sizes, keep, coefs, *, impl: str = "fused",
                    block: int | None = None):
    """Sync server plane over pytrees. ``stacked`` leaves carry a leading
    client axis. ``impl``: see ``_route``.

    The kernel path flattens to one vector per dtype group — ONE
    pallas_call per round per group (flat-staged production params make
    the concat free). The oracle path runs the same single-pass math
    per leaf: inside the round jit that costs no extra dispatch and
    skips the concat/split copies, and per-ELEMENT the op sequence is
    identical either way."""
    kernel, interpret = _route(impl)
    leaves_p, treedef = jax.tree.flatten(prev)
    leaves_s = _co_leaves(stacked, treedef)
    out_leaves = [None] * len(leaves_p)
    if kernel:
        for _, idxs in _dtype_groups(leaves_p).items():
            K = leaves_s[idxs[0]].shape[0]
            fp = _cat([leaves_p[i].reshape(-1) for i in idxs])
            fs = _cat([leaves_s[i].reshape(K, -1) for i in idxs])
            of = server_mix_flat(fp, fs, sizes, keep, coefs, block=block,
                                 interpret=interpret)
            _split_back(of, leaves_p, idxs, out_leaves)
    else:
        for i, (lp, ls) in enumerate(zip(leaves_p, leaves_s)):
            of = ref.server_mix_math(lp.reshape(-1),
                                     ls.reshape(ls.shape[0], -1),
                                     sizes, keep, coefs)
            out_leaves[i] = of.reshape(lp.shape)
    return treedef.unflatten(out_leaves)


def server_mix_compressed_tree(prev, groups, sizes, keep, coefs, *,
                               impl: str = "fused",
                               block: int | None = None):
    """Sync server plane consuming compressed client deltas — the fused
    dequantize-accumulate dispatch behind the mix family's
    ``ServerStrategy.compressed_server_update``.

    ``groups`` is the flat per-dtype-group payload list a
    ``repro.comm`` plane emits from ``compress``: ``(leaf_idxs,
    payload)`` pairs where ``payload`` is either
    ``{"kind": "delta", "d": (K, N) int8|bf16, "scale": (K,) f32}``
    (q8 / bf16 planes) or ``{"kind": "topk", "v": (K, kk) f32,
    "i": (K, kk) int32}`` (top-k sparsification). The leaf grouping is
    the SAME ``_dtype_groups(prev leaves)`` split the dense tree
    drivers use, so one kernel call per round per group consumes the
    compressed bytes. A top-k payload is first densified into a (K, N)
    f32 delta (an XLA scatter) and then takes the delta plane on every
    backend: the Pallas TPU lowering has no scatter-add."""
    from repro.comm.plane import decode     # comm.plane imports this module
    kernel, interpret = _route(impl)
    leaves_p, treedef = jax.tree.flatten(prev)
    out_leaves = [None] * len(leaves_p)
    for idxs, payload in groups:
        fp = _cat([leaves_p[i].reshape(-1) for i in idxs])
        if payload["kind"] == "topk":
            payload = {"kind": "delta", "d": decode(payload, fp.shape[0]),
                       "scale": jnp.ones(payload["v"].shape[:1],
                                         jnp.float32)}
        if payload["kind"] != "delta":
            raise ValueError(f"unknown payload kind {payload['kind']!r}")
        if kernel:
            of = server_mix_delta_flat(
                fp, payload["d"], payload["scale"], sizes, keep, coefs,
                block=block, interpret=interpret)
        else:
            of = ref.server_mix_delta_math(
                fp, payload["d"], payload["scale"], sizes, keep, coefs)
        _split_back(of, leaves_p, idxs, out_leaves)
    return treedef.unflatten(out_leaves)


def server_async_tree(prev, stacked, queue, sizes, delayed, delays, t, hyp,
                      *, impl: str = "fused", block: int | None = None):
    """Async server plane over pytrees: one fused enqueue+pop+mix per
    round. ``queue`` = {"sum": pytree with leading (Q,), "gamma": (Q,)}.
    Returns (new_global, new_queue)."""
    kernel, interpret = _route(impl)
    qgamma = queue["gamma"]
    Q = qgamma.shape[0]
    tq = jnp.stack([jnp.asarray(t, jnp.int32),
                    jnp.asarray(t, jnp.int32) % Q])
    leaves_p, treedef = jax.tree.flatten(prev)
    leaves_s = _co_leaves(stacked, treedef)
    leaves_q = _co_leaves(queue["sum"], treedef)
    out_leaves = [None] * len(leaves_p)
    qs_leaves = [None] * len(leaves_p)
    new_qgamma = qgamma
    if kernel:
        for _, idxs in _dtype_groups(leaves_p).items():
            K = leaves_s[idxs[0]].shape[0]
            fp = _cat([leaves_p[i].reshape(-1) for i in idxs])
            fs = _cat([leaves_s[i].reshape(K, -1) for i in idxs])
            fq = _cat([leaves_q[i].reshape(Q, -1) for i in idxs])
            of, oq, new_qgamma = server_async_flat(
                fp, fs, fq, qgamma, sizes, delayed, delays, tq, hyp,
                block=block, interpret=interpret)
            _split_back(of, leaves_p, idxs, out_leaves)
            _split_back(oq, leaves_q, idxs, qs_leaves)
    else:
        for i, (lp, ls, lq) in enumerate(zip(leaves_p, leaves_s, leaves_q)):
            of, oq, new_qgamma = ref.server_async_math(
                lp.reshape(-1), ls.reshape(ls.shape[0], -1),
                lq.reshape(Q, -1), qgamma, sizes, delayed, delays, tq, hyp)
            out_leaves[i] = of.reshape(lp.shape)
            qs_leaves[i] = oq.reshape(lq.shape)
    return (treedef.unflatten(out_leaves),
            {"sum": treedef.unflatten(qs_leaves), "gamma": new_qgamma})


def server_adam_tree(prev, stacked, m, v, sizes, keep, scalars, *,
                     impl: str = "fused", block: int | None = None):
    """FedOpt server plane over pytrees. ``m``/``v`` are f32 trees shaped
    like ``prev``. Returns (new_global, new_m, new_v)."""
    kernel, interpret = _route(impl)
    leaves_p, treedef = jax.tree.flatten(prev)
    leaves_s = _co_leaves(stacked, treedef)
    leaves_m = _co_leaves(m, treedef)
    leaves_v = _co_leaves(v, treedef)
    out_leaves = [None] * len(leaves_p)
    m_leaves = [None] * len(leaves_p)
    v_leaves = [None] * len(leaves_p)
    if kernel:
        for _, idxs in _dtype_groups(leaves_p).items():
            K = leaves_s[idxs[0]].shape[0]
            fp = _cat([leaves_p[i].reshape(-1) for i in idxs])
            fs = _cat([leaves_s[i].reshape(K, -1) for i in idxs])
            fm = _cat([leaves_m[i].reshape(-1) for i in idxs])
            fv = _cat([leaves_v[i].reshape(-1) for i in idxs])
            of, om, ov = server_adam_flat(fp, fs, fm, fv, sizes, keep,
                                          scalars, block=block,
                                          interpret=interpret)
            _split_back(of, leaves_p, idxs, out_leaves)
            _split_back(om, leaves_m, idxs, m_leaves)
            _split_back(ov, leaves_v, idxs, v_leaves)
    else:
        for i, (lp, ls, lm, lv) in enumerate(
                zip(leaves_p, leaves_s, leaves_m, leaves_v)):
            of, om, ov = ref.server_adam_math(
                lp.reshape(-1), ls.reshape(ls.shape[0], -1),
                lm.reshape(-1), lv.reshape(-1), sizes, keep, scalars)
            out_leaves[i] = of.reshape(lp.shape)
            m_leaves[i] = om.reshape(lm.shape)
            v_leaves[i] = ov.reshape(lv.shape)
    return (treedef.unflatten(out_leaves), treedef.unflatten(m_leaves),
            treedef.unflatten(v_leaves))
