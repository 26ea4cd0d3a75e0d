"""Plain reference of the E5 stencils that the benchmark runs.

Written from the stencils' definitions (paper Listing 4 for jacobi; the
KernelGen tricubic interpolation: a 4x4x4 Catmull-Rom-like tap set with
weights (-1/16, 9/16, 9/16, -1/16) per axis, plus the three fractional
coordinate arrays), in straightforward jax.numpy.  Arrays are stored with
the thread index i as the last axis: 2D (nj, ni), 3D (nk, nj, ni).  The
output covers the interior, the full shape less ``HALO`` on each side.
Sums run in the order the definitions write them.

``dtype`` is the precision of the computation: float32 as the
configuration states, or bfloat16 for the control.
"""

from __future__ import annotations

import jax.numpy as jnp

HALO = {"jacobi": 1, "tricubic": 2}
INPUTS = {"jacobi": ("w0",), "tricubic": ("s", "u", "v", "w0")}
TRICUBIC_W = (-0.0625, 0.5625, 0.5625, -0.0625)


def _shift(x, halo, offsets):
    """Interior view of ``x`` shifted by ``offsets`` (one per axis, in
    array-axis order)."""
    return x[tuple(slice(halo + o, n - halo + o)
                   for o, n in zip(offsets, x.shape))]


def jacobi(arrays, scalars, dtype=jnp.float32):
    h = HALO["jacobi"]
    w = arrays["w0"].astype(dtype)
    c0, c1, c2 = (jnp.asarray(scalars[c], dtype) for c in ("c0", "c1", "c2"))

    def at(di, dj):
        return _shift(w, h, (dj, di))

    return (c0 * at(0, 0)
            + c1 * (at(-1, 0) + at(0, -1) + at(1, 0) + at(0, 1))
            + c2 * (at(-1, -1) + at(-1, 1) + at(1, -1) + at(1, 1)))


def tricubic(arrays, scalars, dtype=jnp.float32):
    h = HALO["tricubic"]
    w = arrays["w0"].astype(dtype)
    acc = None
    for dk in range(-1, 3):
        for dj in range(-1, 3):
            for di in range(-1, 3):
                c = TRICUBIC_W[di + 1] * TRICUBIC_W[dj + 1] * TRICUBIC_W[dk + 1]
                t = jnp.asarray(c, dtype) * _shift(w, h, (dk, dj, di))
                acc = t if acc is None else acc + t
    frac = (_shift(arrays["u"].astype(dtype), h, (0, 0, 0))
            + _shift(arrays["v"].astype(dtype), h, (0, 0, 0))
            + _shift(arrays["s"].astype(dtype), h, (0, 0, 0)))
    return acc + frac


STENCILS = {"jacobi": jacobi, "tricubic": tricubic}


def evaluate(name, arrays, scalars, dtype=jnp.float32):
    """The interior output of stencil ``name``, as float32."""
    return STENCILS[name](arrays, scalars, dtype).astype(jnp.float32)
