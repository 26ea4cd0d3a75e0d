"""Public jit'd entry points for the Pallas stencil kernel.

``stencil_apply`` pads the interior up to the block grid, runs the
Pallas kernel (compiled for the TPU; ``interpret=True`` runs it in the
Pallas interpreter, e.g. on the CPU), and slices the true interior back
out — so arbitrary problem sizes work (the paper's "fractional threads"
corner case, resolved here by padding geometry instead of predication).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from repro.core.frontend.stencil import Program
from .stencil import (DEFAULT_BLOCKS, MODES, build_stencil,
                      hbm_bytes_per_block, make_plan)
from . import ref as stencil_ref


def stencil_apply(prog: Program, arrays: Dict[str, jnp.ndarray],
                  scalars: Optional[Dict[str, float]] = None,
                  mode: str = "tile",
                  block: Optional[Tuple[int, ...]] = None,
                  interpret: bool = False,
                  trace_every: int = 0) -> jnp.ndarray:
    """Run the stencil program; returns the interior-shaped output.

    Each input is edge-padded on the high side until the interior is a
    block multiple and every tile-widened fetch window of the last block
    stays in bounds (``FetchPlan.extent``).  ``trace_every`` > 0 makes
    every that-many-th grid step record the kernel's trace regions
    (:func:`build_stencil`).
    """
    assert mode in MODES
    block = tuple(block) if block else DEFAULT_BLOCKS[prog.ndim]
    first = next(iter(arrays.values()))
    interior = stencil_ref.interior_shape(first.shape, prog.halo)
    grid_interior = tuple(-(-n // b) * b for n, b in zip(interior, block))
    extent = make_plan(prog, mode).extent(grid_interior, block,
                                          first.dtype.itemsize)
    pads = [(0, e - n) for e, n in zip(extent, first.shape)]
    padded = {name: jnp.pad(x, pads, mode="edge") if any(p for _, p in pads)
              else x for name, x in arrays.items()}
    fn = build_stencil(prog, mode=mode, block=block, scalars=scalars,
                       interpret=interpret, trace_every=trace_every)
    out = fn(padded, grid_interior)
    return out[tuple(slice(0, n) for n in interior)]


def reference(prog: Program, arrays: Dict[str, jnp.ndarray],
              scalars: Optional[Dict[str, float]] = None) -> jnp.ndarray:
    """The pure-jnp oracle (same interior-shaped output)."""
    return stencil_ref.evaluate(prog, arrays, scalars)


def traffic_report(prog: Program, shape: Tuple[int, ...],
                   block: Optional[Tuple[int, ...]] = None) -> Dict[str, float]:
    """Analytic HBM read traffic per mode for a full problem, in bytes.

    This is the TPU counterpart of the paper's load-count reduction
    (Table 2 Shuffle/Load): bytes(naive)/bytes(mode) bounds the
    memory-side speedup of shuffle synthesis on a bandwidth-bound chip.
    """
    block = tuple(block) if block else DEFAULT_BLOCKS[prog.ndim]
    nd = prog.ndim
    halo = prog.halo
    interior = [shape[a] - 2 * halo[nd - 1 - a] for a in range(nd)]
    n_blocks = 1
    for a in range(nd):
        n_blocks *= -(-interior[a] // block[a])
    out = {}
    for mode in MODES:
        out[mode] = float(hbm_bytes_per_block(prog, mode, block) * n_blocks)
    out["reduction_paper"] = out["naive"] / out["paper"]
    out["reduction_tile"] = out["naive"] / out["tile"]
    return out
