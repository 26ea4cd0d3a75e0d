"""SSD Pallas kernel: shape/dtype sweeps vs the chunked-scan oracle,
including the cross-chunk VMEM-scratch state carry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd import ssd_pallas, ssd_ref

RNG = np.random.default_rng(0)


def _inputs(B, L, H, P, N, dtype):
    xh = jnp.asarray(RNG.standard_normal((B, L, H, P)), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, L, 1, N)), dtype)
    Cm = jnp.asarray(RNG.standard_normal((B, L, 1, N)), dtype)
    return xh, dt, A, Bm, Cm


@pytest.mark.parametrize("shape", [(2, 64, 4, 16, 16, 16),
                                   (1, 128, 2, 32, 64, 32),
                                   (2, 96, 3, 8, 16, 32),
                                   (1, 64, 2, 16, 16, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_matches_oracle(shape, dtype):
    B, L, H, P, N, Q = shape
    xh, dt, A, Bm, Cm = _inputs(B, L, H, P, N, dtype)
    ref = ssd_ref(xh, dt, A, Bm, Cm, chunk=Q)
    out = ssd_pallas(xh, dt, A, Bm, Cm, chunk=Q, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_ssd_state_carries_across_chunks():
    """Single long chunk == many short chunks (scratch carry exactness)."""
    B, L, H, P, N = 1, 64, 2, 8, 16
    xh, dt, A, Bm, Cm = _inputs(B, L, H, P, N, jnp.float32)
    one = ssd_pallas(xh, dt, A, Bm, Cm, chunk=64, interpret=True)
    many = ssd_pallas(xh, dt, A, Bm, Cm, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               rtol=2e-4, atol=2e-4)
