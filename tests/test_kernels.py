"""The sync server-plane kernel body (interpret mode — executes the
Pallas kernel on CPU) against its jnp oracle over a sweep of sizes:
padding (N not a multiple of 128 lanes), one block and many blocks;
K = 1, a few, and the paper CNN cell's 10 clients a round."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.server_plane import server_mix_flat


@pytest.mark.parametrize("N", [100, 1024, 4096 + 17])
@pytest.mark.parametrize("K", [1, 4, 10])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_server_mix_sweep(N, K, dtype):
    rng = np.random.RandomState(N + K)
    prev = jnp.asarray(rng.randn(N), dtype)
    stacked = jnp.asarray(rng.randn(K, N), dtype)
    sizes = jnp.asarray(rng.rand(K) + 0.5, jnp.float32)
    keep = jnp.asarray((rng.rand(K) < 0.7).astype(np.float32))
    coefs = jnp.asarray([0.1, 2.5e-3, 0.95, 7.0], jnp.float32)
    got = server_mix_flat(prev, stacked, sizes, keep, coefs, block=1024,
                          interpret=True)
    want = ref.server_mix_math(prev, stacked, sizes, keep, coefs)
    assert got.dtype == prev.dtype and got.shape == (N,)
    # the same op sequence on both sides: 1-2 ulp of the dtype
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
