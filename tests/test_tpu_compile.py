"""Compile every server-plane Pallas kernel for a TPU v5e, without a chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described rather than attached (``topologies.get_topology_desc``).
That catches what the interpret-mode parity tests cannot: VMEM
overflow, tiles the Mosaic layout rejects, and primitives the Pallas
TPU lowering lacks. Shapes: the paper CNN (N = 54,784 params) and one
transformer-sized dtype group (N = 4096 * 16384, one minitron-8b MLP
matrix), with K = 10 clients per round and a Q = 16 ring buffer (the
paper's 15 rounds of delay), in f32 and bf16, and int8 payloads for the
compressed-delta plane.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, so the
test workers must all collect the same tests and only the one running
this file may load it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import server_plane as sp

K, Q = 10, 16
SIZES = {"paper_cnn": 54_784, "mlp_group": 4096 * 16384}
F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_args(kernel, N, dtype, S):
    """(fn, shapes) of one kernel call; ``S(shape, dtype)`` places a
    shape on the described chip."""
    vec = S((K,), F32)
    if kernel == "mix":
        return sp.server_mix_flat, (S((N,), dtype), S((K, N), dtype), vec,
                                    vec, S((4,), F32))
    if kernel.startswith("delta"):
        qd = jnp.int8 if kernel == "delta_int8" else dtype
        return sp.server_mix_delta_flat, (S((N,), dtype), S((K, N), qd),
                                          vec, vec, vec, S((4,), F32))
    if kernel == "async":
        return sp.server_async_flat, (
            S((N,), dtype), S((K, N), dtype), S((Q, N), F32), S((Q,), F32),
            vec, vec, S((K,), jnp.int32), S((2,), jnp.int32), S((4,), F32))
    assert kernel == "adam"
    return sp.server_adam_flat, (S((N,), dtype), S((K, N), dtype),
                                 S((N,), F32), S((N,), F32), vec, vec,
                                 S((5,), F32))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("kernel", ["mix", "delta_int8", "delta_same",
                                    "async", "adam"])
def test_server_plane_kernel_compiles_for_v5e(one_chip, kernel, size,
                                              dtype):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn, args = _kernel_args(kernel, SIZES[size], dtype, S)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("streams,temps", [
    ([(2, F32), (K, F32)], 3),                              # mix, f32
    ([(2, jnp.bfloat16), (K, jnp.int8)], 3),                # q8 delta
    ([(2, F32), (K, F32), (2 * Q, F32)], Q + 3),            # async
    ([(2, jnp.bfloat16), (K, jnp.bfloat16), (4, F32)], 7),  # adam, bf16
])
def test_block_rows_fit_the_vmem_budget(streams, temps):
    """The derived tile is a whole number of sublane tiles of every
    operand, its double-buffered streams and temporaries fit
    ``VMEM_BUDGET``, and an N that fits one block gets a block holding
    every row."""
    R = SIZES["mlp_group"] // sp.LANES
    br = sp._block_rows(R, streams, temps, None)
    tile = max(sp._sublanes(d) for _, d in streams)
    assert 0 < br < R and br % tile == 0
    per_row = sp.LANES * (2 * sum(n * jnp.dtype(d).itemsize
                                  for n, d in streams) + 4 * temps)
    assert br * per_row <= sp.VMEM_BUDGET
    small = SIZES["paper_cnn"] // sp.LANES
    assert sp._block_rows(small, streams, temps, None) == min(small, br)
