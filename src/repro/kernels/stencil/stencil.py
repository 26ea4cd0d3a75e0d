"""Pallas TPU stencil kernel with shuffle-synthesized data reuse.

This is the TPU-native port of the paper's shuffle synthesis (DESIGN.md
§2).  A GPU warp's lanes become the lane dimension of a VMEM tile; the
``shfl.sync.up/down N`` register exchange becomes a *static shifted
slice* of a tile already resident in VMEM — the halo columns of the tile
play the role of the paper's corner-case loads, resolved at compile time
instead of per-thread predication.

Three fetch plans, mirroring the paper's ablation structure:

``naive``   one HBM fetch per static load in the PTX (the *Original*):
            every tap of every array is a separate (Bk,Bj,Bi) fetch.
``paper``   PTXASW-faithful: loads that the symbolic emulator proved
            shuffle-coverable (same array, same non-leading offsets,
            constant lane delta) share ONE row fetch widened by the
            lane span; uncovered loads stay separate fetches.  This is
            exactly the paper's "source load + shfl" reuse, with the
            lane shift realized as a static slice.
``tile``    beyond-paper TPU-native plan: ONE halo tile per array,
            every tap a shifted slice in *all* dims (the multi-dim
            generalization the warp cannot express).

The kernel keeps inputs in ``pl.ANY`` (HBM) and stages every fetch
through VMEM scratch explicitly: one DMA per :class:`Fetch`, its window
widened to whole (8, 128) memory tiles (:mod:`repro.kernels.dma`), so
the HBM traffic of each plan is visible both in the analytic model
(:func:`hbm_bytes_per_block`, which counts the widened windows) and in
the lowered IR.  Correctness is validated against
:mod:`repro.kernels.stencil.ref` (the pure-jnp oracle), in interpret mode
on the CPU and compiled on the chip.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.frontend.stencil import (
    Bin,
    Call,
    Const,
    Expr,
    Load,
    Program,
    Scalar,
    collect_loads,
)
from ..dma import aligned_window, axis_tiles
from .ref import _CALLS, tap_offsets

MODES = ("naive", "paper", "tile")

DEFAULT_BLOCKS = {1: (256,), 2: (8, 128), 3: (1, 8, 128)}

# the trace regions of a sampled grid step, in the order they run
REGIONS = ("stencil_dma_issue", "stencil_dma_wait", "stencil_compute")


# ---------------------------------------------------------------------------
# fetch planning
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Fetch:
    """One HBM->VMEM transfer: per-dim (lo, hi) tap extents around the
    output block, ordered (i, j, k).  Serves ``taps`` (offset tuples)."""

    array: str
    lo: Tuple[int, ...]
    hi: Tuple[int, ...]
    taps: Tuple[Tuple[int, ...], ...]

    def window(self, block: Sequence[int], halo: Sequence[int],
               itemsize: int = 4) -> Tuple[Tuple[int, int], ...]:
        """The DMA window per array axis (k, j, i), as ``(base, length)``
        relative to the block's corner in the halo-padded array, widened
        to whole memory tiles.  ``block`` is in array-axis order; ``halo``,
        ``lo`` and ``hi`` are in dim order (i, j, k)."""
        nd = len(self.lo)
        tiles = axis_tiles(nd, itemsize)
        return tuple(
            aligned_window(halo[nd - 1 - a] + self.lo[nd - 1 - a],
                           block[a] + self.hi[nd - 1 - a] - self.lo[nd - 1 - a],
                           tiles[a])
            for a in range(nd))


@dataclass
class FetchPlan:
    mode: str
    fetches: List[Fetch]
    halo: Tuple[int, ...] = ()

    def windows(self, block: Sequence[int], itemsize: int = 4):
        return [f.window(block, self.halo, itemsize) for f in self.fetches]

    def bytes_per_block(self, block: Sequence[int], itemsize: int = 4) -> int:
        """HBM bytes the block's DMAs copy (tile-widened windows)."""
        return itemsize * sum(math.prod(n for _, n in win)
                              for win in self.windows(block, itemsize))

    def extent(self, interior: Sequence[int], block: Sequence[int],
               itemsize: int = 4) -> Tuple[int, ...]:
        """Array extent per axis that a grid of ``block``-sized output
        blocks over ``interior`` (a block multiple) may read: the halo'd
        interior, or further where the last block's widened windows reach
        past it."""
        nd = len(block)
        wins = self.windows(block, itemsize)
        return tuple(
            max([interior[a] + 2 * self.halo[nd - 1 - a]]
                + [interior[a] - block[a] + base + n
                   for base, n in (w[a] for w in wins)])
            for a in range(nd))


def _unique_taps(prog: Program) -> List[Tuple[str, Tuple[int, ...]]]:
    seen = []
    for ld in collect_loads(prog.expr):
        key = (ld.array, tap_offsets(ld, prog.ndim))
        if key not in seen:
            seen.append(key)
    return seen


def make_plan(prog: Program, mode: str) -> FetchPlan:
    assert mode in MODES
    taps = _unique_taps(prog)
    nd = prog.ndim
    fetches: List[Fetch] = []
    if mode == "naive":
        for arr, off in taps:
            fetches.append(Fetch(arr, off, off, (off,)))
    elif mode == "paper":
        # group by (array, non-leading offsets): the emulator's shuffle rows
        rows: Dict[Tuple, List[Tuple[int, ...]]] = {}
        for arr, off in taps:
            rows.setdefault((arr, off[1:]), []).append(off)
        for (arr, _rest), offs in rows.items():
            lo = (min(o[0] for o in offs),) + offs[0][1:]
            hi = (max(o[0] for o in offs),) + offs[0][1:]
            fetches.append(Fetch(arr, lo, hi, tuple(offs)))
    else:  # tile
        per_array: Dict[str, List[Tuple[int, ...]]] = {}
        for arr, off in taps:
            per_array.setdefault(arr, []).append(off)
        for arr, offs in per_array.items():
            lo = tuple(min(o[d] for o in offs) for d in range(nd))
            hi = tuple(max(o[d] for o in offs) for d in range(nd))
            fetches.append(Fetch(arr, lo, hi, tuple(offs)))
    return FetchPlan(mode, fetches, prog.halo)


def hbm_bytes_per_block(prog: Program, mode: str,
                        block: Sequence[int], itemsize: int = 4) -> int:
    return make_plan(prog, mode).bytes_per_block(block, itemsize)


# ---------------------------------------------------------------------------
# kernel construction
# ---------------------------------------------------------------------------

def _no_scope(name: str):
    return contextlib.nullcontext()


def _build_kernel(prog: Program, plan: FetchPlan, block: Tuple[int, ...],
                  scalars: Dict[str, float], array_names: List[str],
                  itemsize: int, trace_every: int = 0):
    nd = prog.ndim
    halo = prog.halo
    windows = plan.windows(block, itemsize)
    tiles = axis_tiles(nd, itemsize)
    issue_region, wait_region, compute_region = REGIONS

    def step(refs, corner, scope):
        n_in = len(array_names)
        in_refs = dict(zip(array_names, refs[:n_in]))
        out_ref = refs[n_in]
        bufs, sem = refs[n_in + 1:-1], refs[-1]

        # stage fetches: one tile-aligned DMA per fetch, all in flight
        copies = []
        with scope(issue_region):
            for n, (f, win) in enumerate(zip(plan.fetches, windows)):
                idx = []
                for a, (base, length) in enumerate(win):
                    start = corner[a] + base
                    if tiles[a] > 1 and block[a] % tiles[a] == 0:
                        start = pl.multiple_of(start, tiles[a])
                    idx.append(pl.ds(start, length))
                cp = pltpu.make_async_copy(in_refs[f.array].at[tuple(idx)],
                                           bufs[n], sem.at[n])
                cp.start()
                copies.append(cp)
        with scope(wait_region):
            for cp in copies:
                cp.wait()

        # tap offsets -> loaded values
        tap_val: Dict[Tuple[str, Tuple[int, ...]], jnp.ndarray] = {}
        for n, (f, win) in enumerate(zip(plan.fetches, windows)):
            for off in f.taps:
                sl = []
                for a, (base, _) in enumerate(win):
                    begin = halo[nd - 1 - a] + off[nd - 1 - a] - base
                    sl.append(slice(begin, begin + block[a]))
                # static shifted slice of the staged buffer — the TPU
                # analogue of shfl.sync with delta (off - source)
                tap_val[(f.array, off)] = bufs[n][tuple(sl)]

        def ev(e: Expr) -> jnp.ndarray:
            if isinstance(e, Load):
                return tap_val[(e.array, tap_offsets(e, nd))]
            if isinstance(e, Const):
                return jnp.float32(e.value)
            if isinstance(e, Scalar):
                return jnp.float32(scalars[e.name])
            if isinstance(e, Bin):
                a, b = ev(e.a), ev(e.b)
                return {"+": jnp.add, "-": jnp.subtract,
                        "*": jnp.multiply, "/": jnp.divide}[e.op](a, b)
            if isinstance(e, Call):
                return _CALLS[e.fn](ev(e.arg))
            raise TypeError(e)

        with scope(compute_region):
            out_ref[...] = ev(prog.expr).astype(out_ref.dtype)

    def kernel(*refs):
        # block corner per array axis (k.., j, i) in the halo-padded array
        corner = [pl.program_id(a) * block[a] for a in range(nd)]
        if not trace_every:
            step(refs, corner, _no_scope)
            return
        # grid steps whose linear index is a multiple of trace_every
        # record REGIONS; the others run the same work unrecorded
        linear = pl.program_id(0)
        for a in range(1, nd):
            linear = linear * pl.num_programs(a) + pl.program_id(a)
        sampled = linear % trace_every == 0
        pl.when(sampled)(lambda: step(refs, corner, jax.named_scope))
        pl.when(jnp.logical_not(sampled))(
            lambda: step(refs, corner, _no_scope))

    return kernel, windows


def build_stencil(prog: Program, mode: str = "tile",
                  block: Optional[Tuple[int, ...]] = None,
                  scalars: Optional[Dict[str, float]] = None,
                  interpret: bool = False, trace_every: int = 0):
    """Build a callable ``f(arrays: dict, interior) -> output`` running
    the stencil as a Pallas kernel with the given fetch plan.

    ``interior`` is the output shape, a multiple of the block per axis;
    each array must extend to at least ``plan.extent(interior, block)``
    so that every widened DMA window stays in bounds.  Use
    :func:`repro.kernels.stencil.ops.stencil_apply` for auto-padding.

    With ``trace_every`` N > 0, grid steps whose linear index is a
    multiple of N record the named trace regions :data:`REGIONS` (DMA
    issue, DMA wait, compute), which a device profile shows where the
    compiler is asked for custom-call region traces; 0 builds the kernel
    without any trace op.
    """
    assert mode in MODES
    assert trace_every >= 0
    block = tuple(block) if block else DEFAULT_BLOCKS[prog.ndim]
    assert len(block) == prog.ndim
    plan = make_plan(prog, mode)
    scalars = dict(scalars or {})
    array_names = sorted(a for a in prog.arrays if a != prog.out.array)
    nd = prog.ndim

    def apply_fn(arrays: Dict[str, jnp.ndarray],
                 interior: Tuple[int, ...]) -> jnp.ndarray:
        first = arrays[array_names[0]]
        itemsize = first.dtype.itemsize
        if any(interior[a] % block[a] for a in range(nd)):
            raise ValueError(
                f"interior {interior} not divisible by block {block}")
        need = plan.extent(interior, block, itemsize)
        if any(first.shape[a] < need[a] for a in range(nd)):
            raise ValueError(f"arrays of shape {first.shape} are smaller "
                             f"than the {need} the fetch windows read")
        kernel, windows = _build_kernel(prog, plan, block, scalars,
                                        array_names, itemsize, trace_every)
        scratch = [pltpu.VMEM(tuple(n for _, n in win), first.dtype)
                   for win in windows]
        scratch.append(pltpu.SemaphoreType.DMA((len(windows),)))
        fn = pl.pallas_call(
            kernel,
            grid=tuple(interior[a] // block[a] for a in range(nd)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)
                      for _ in array_names],
            out_specs=pl.BlockSpec(block, lambda *p: p),
            out_shape=jax.ShapeDtypeStruct(interior, jnp.float32),
            scratch_shapes=scratch,
            interpret=interpret,
        )
        return fn(*[arrays[a] for a in array_names])

    return apply_fn
