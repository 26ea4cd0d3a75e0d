"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp oracles,
plus the PTXASW <-> kernel-plan consistency properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:               # degrade: property tests skip
    from _hypothesis_stub import given, settings, st

from repro.core.frontend.kernelgen import all_benches, get_bench
from repro.core.frontend.pallas_lower import synthesize_tpu
from repro.kernels.conv1d import causal_conv1d, hbm_bytes
from repro.kernels.conv1d import ref as conv_ref
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.stencil import make_plan, reference, stencil_apply

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# stencil kernel
# ---------------------------------------------------------------------------

STENCIL_BENCHES = ["jacobi", "gaussblur", "laplacian", "wave13pt",
                   "whispering", "gradient", "divergence", "gameoflife",
                   "lapgsrb", "uxx1", "tricubic", "sincos", "vecadd"]


@pytest.mark.parametrize("name", STENCIL_BENCHES)
@pytest.mark.parametrize("mode", ["naive", "paper", "tile"])
def test_stencil_matches_oracle(name, mode):
    b = get_bench(name)
    prog = b.program
    nd = prog.ndim
    shape = {1: (300,), 2: (20, 140), 3: (6, 20, 140)}[nd]
    arrays = {a: jnp.asarray(RNG.standard_normal(shape[-d:]), jnp.float32)
              for a, d in prog.arrays.items() if a != prog.out.array}
    scalars = {s: float(RNG.uniform(0.1, 1.0)) for s in prog.scalars}
    ref = reference(prog, arrays, scalars)
    out = stencil_apply(prog, arrays, scalars, mode=mode,
                        block={1: (64,), 2: (8, 32), 3: (1, 8, 32)}[nd],
                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", ["jacobi", "tricubic"])
def test_stencil_with_trace_regions_matches_oracle(name):
    """Every third grid step records the kernel's trace regions; the
    output still equals the oracle, and the plain kernel's bit for bit."""
    prog = get_bench(name).program
    shape = {2: (20, 140), 3: (6, 20, 140)}[prog.ndim]
    arrays = {a: jnp.asarray(RNG.standard_normal(shape), jnp.float32)
              for a in prog.arrays if a != prog.out.array}
    scalars = {s: float(RNG.uniform(0.1, 1.0)) for s in prog.scalars}
    block = {2: (8, 32), 3: (1, 8, 32)}[prog.ndim]
    out, plain = (stencil_apply(prog, arrays, scalars, mode="paper",
                                block=block, interpret=True, trace_every=n)
                  for n in (3, 0))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(reference(prog, arrays, scalars)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))


# (program, interior, block): grids whose fetch ring crosses rows (4x4
# steps), rows and planes (2x2x3), and grids shorter than the ring
RING_GRIDS = {
    "rows": ("jacobi", (32, 128), (8, 32)),
    "planes": ("tricubic", (2, 16, 96), (1, 8, 32)),
    "one_step": ("gaussblur", (8, 32), (8, 32)),
    "two_steps": ("laplacian", (2, 8, 32), (1, 8, 32)),
}


def _check_ring(grid, mode):
    """The kernel's output on one of RING_GRIDS equals the oracle's, and
    a region-sampling build's (every other step) equals it bit for bit."""
    name, interior, block = RING_GRIDS[grid]
    prog = get_bench(name).program
    nd = prog.ndim
    shape = tuple(n + 2 * prog.halo[nd - 1 - a]
                  for a, n in enumerate(interior))
    arrays = {a: jnp.asarray(RNG.standard_normal(shape[-d:]), jnp.float32)
              for a, d in prog.arrays.items() if a != prog.out.array}
    scalars = {s: float(RNG.uniform(0.1, 1.0)) for s in prog.scalars}
    out, plain = (stencil_apply(prog, arrays, scalars, mode=mode,
                                block=block, interpret=True, trace_every=n)
                  for n in (2, 0))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(reference(prog, arrays, scalars)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))


@pytest.mark.parametrize("mode", ["naive", "paper", "tile"])
@pytest.mark.parametrize("grid", sorted(RING_GRIDS))
def test_stencil_fetch_ring_matches_oracle(grid, mode):
    _check_ring(grid, mode)


@pytest.mark.parametrize("grid", sorted(RING_GRIDS))
def test_stencil_fetch_ring_fallback_depth(grid, monkeypatch):
    """The depth-2 ring that a block past the VMEM budget gets."""
    from repro.kernels.stencil import stencil as stencil_mod
    monkeypatch.setattr(stencil_mod, "VMEM_BUDGET", 0)
    _check_ring(grid, "paper")


def test_ring_depth_rule():
    """RING_DEPTH slots while they and the output's two blocks fit the
    VMEM budget, 2 past it; every default block of every plan fits."""
    from repro.kernels.stencil import DEFAULT_BLOCKS, hbm_bytes_per_block
    from repro.kernels.stencil.stencil import (RING_DEPTH, VMEM_BUDGET,
                                               ring_depth)
    out_block = 4 * 8 * 128
    fits = (VMEM_BUDGET - 2 * out_block) // RING_DEPTH
    assert ring_depth(0, out_block) == RING_DEPTH
    assert ring_depth(fits, out_block) == RING_DEPTH
    assert ring_depth(fits + 1, out_block) == 2
    assert ring_depth(VMEM_BUDGET, out_block) == 2
    for name in STENCIL_BENCHES:
        prog = get_bench(name).program
        block = DEFAULT_BLOCKS[prog.ndim]
        for mode in ("naive", "paper", "tile"):
            assert ring_depth(hbm_bytes_per_block(prog, mode, block),
                              4 * np.prod(block)) == RING_DEPTH


@pytest.mark.parametrize("name", sorted(all_benches(include_apps=True)))
def test_detection_plan_consistency(name):
    """The symbolic emulator's shuffle count must equal the geometric
    row-coverable tap count of the Pallas 'paper' plan (DESIGN.md §2)."""
    b = all_benches(include_apps=True)[name]
    plan = synthesize_tpu(b.program, max_delta=b.max_delta)
    assert plan.consistent


def test_traffic_ordering():
    """tile <= paper <= naive bytes for every stencil."""
    for name in ("jacobi", "gaussblur", "tricubic", "lapgsrb"):
        prog = get_bench(name).program
        block = {2: (8, 128), 3: (1, 8, 128)}[prog.ndim]
        naive = make_plan(prog, "naive").bytes_per_block(block)
        paper = make_plan(prog, "paper").bytes_per_block(block)
        tile = make_plan(prog, "tile").bytes_per_block(block)
        assert tile <= paper <= naive


# ---------------------------------------------------------------------------
# conv1d (Mamba-2 integration of the paper's technique)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 64, 32, 4), (1, 100, 48, 4),
                                   (3, 33, 17, 3), (2, 256, 96, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["naive", "shuffle"])
def test_conv1d_matches_oracle(shape, dtype, mode):
    B, L, C, W = shape
    x = jnp.asarray(RNG.standard_normal((B, L, C)), dtype)
    w = jnp.asarray(RNG.standard_normal((W, C)), dtype)
    b = jnp.asarray(RNG.standard_normal((C,)), dtype)
    ref = conv_ref.causal_conv1d(x, w, b)
    out = causal_conv1d(x, w, b, mode=mode, block_seq=32, block_ch=16,
                        interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_conv1d_traffic_reduction():
    r = hbm_bytes(4096, 4096, 4, "naive") / hbm_bytes(4096, 4096, 4, "shuffle")
    assert r > 3.5   # W=4 taps -> ~4x fewer HBM reads


def test_ptxasw_finds_conv_deltas():
    """The paper's analysis applied to the Mamba conv pattern: a width-4
    causal 1D stencil yields 3 shuffles with deltas {1,2,3}."""
    from repro.core.frontend.stencil import Array, I, Program, lower_to_ptx
    from repro.core.synthesis.pipeline import ptxasw_kernel
    x = Array("x")
    expr = (0.1 * x[I(-3)] + 0.2 * x[I(-2)] + 0.3 * x[I(-1)] + 0.4 * x[I(0)])
    prog = Program(name="conv1d", ndim=1, out=Array("y")[I()], expr=expr)
    _, rep = ptxasw_kernel(lower_to_ptx(prog))
    deltas = sorted(p.delta for p in rep.detection.pairs)
    assert deltas == [1, 2, 3]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 64, 64, 4, 2, 16, True),
                                   (1, 100, 100, 4, 4, 8, True),
                                   (2, 64, 64, 8, 2, 16, False),
                                   (1, 33, 33, 2, 1, 32, True),
                                   (2, 48, 96, 4, 1, 16, True)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(shape, dtype):
    B, Sq, Sk, H, KV, Dh, causal = shape
    q = jnp.asarray(RNG.standard_normal((B, Sq, H, Dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Sk, KV, Dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Sk, KV, Dh)), dtype)
    ref = attention_ref(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.integers(17, 80), st.integers(1, 2),
       st.sampled_from([8, 16]))
def test_flash_attention_property(B, S, KV, Dh):
    H = KV * 2
    q = jnp.asarray(RNG.standard_normal((B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KV, Dh)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KV, Dh)), jnp.float32)
    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
