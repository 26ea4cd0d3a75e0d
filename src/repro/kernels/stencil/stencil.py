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
the lowered IR.

The fetches run ahead of the compute in a ring of ``depth`` slots
(:func:`ring_depth`).  The grid runs in order, and each grid step first
starts the fetches of the step ``depth - 1`` ahead of it in row-major
order (across rows and planes) into that step's slot, then waits for its
own slot, which the step ``depth - 1`` before it filled, and computes
from it; step 0 also starts the steps before the first look-ahead.  So
each step's DMA latency runs under the compute of the steps before it,
while the fetch plan, and every byte it copies, stays the same.
Correctness is validated against
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
from jax import lax
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

# slots of the fetch ring: one step computing while the next three
# fetch; on a v5e, 4 ran both E5 cells faster than 3, and 3 than 2
RING_DEPTH = 4
# VMEM the ring's slots and the output's two pipelined blocks may take:
# half the 16 MiB scoped by default, the rest left to the compute
VMEM_BUDGET = 8 * 2 ** 20


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

def ring_depth(bytes_per_block: int, out_block_bytes: int) -> int:
    """Slots of the fetch ring for a block whose fetch windows take
    ``bytes_per_block`` of VMEM and whose output block takes
    ``out_block_bytes``: :data:`RING_DEPTH`, or 2 where that many slots
    beside the output's two pipelined blocks would pass
    :data:`VMEM_BUDGET`."""
    if RING_DEPTH * bytes_per_block + 2 * out_block_bytes > VMEM_BUDGET:
        return 2
    return RING_DEPTH


def _no_scope(name: str):
    return contextlib.nullcontext()


def _build_kernel(prog: Program, plan: FetchPlan, block: Tuple[int, ...],
                  grid: Tuple[int, ...], depth: int,
                  scalars: Dict[str, float], array_names: List[str],
                  itemsize: int, trace_every: int = 0):
    nd = prog.ndim
    halo = prog.halo
    windows = plan.windows(block, itemsize)
    tiles = axis_tiles(nd, itemsize)
    n_steps = math.prod(grid)
    # row-major strides of the grid: a step's linear index is the order
    # in which the sequential grid runs it
    strides = [math.prod(grid[a + 1:]) for a in range(nd)]
    issue_region, wait_region, compute_region = REGIONS

    def unravel(t):
        """Block corner per array axis (k.., j, i), in the halo-padded
        array, of the grid step with linear index ``t``."""
        corner = []
        for a in range(nd):
            idx = t if strides[a] == 1 else lax.div(t, strides[a])
            if a:
                idx = lax.rem(idx, grid[a])
            corner.append(idx * block[a])
        return corner

    def copies(in_refs, bufs, sem, corner, slot):
        """One tile-aligned DMA per fetch of the block at ``corner``,
        into ring slot ``slot``."""
        out = []
        for n, (f, win) in enumerate(zip(plan.fetches, windows)):
            idx = []
            for a, (base, length) in enumerate(win):
                start = corner[a] + base
                if tiles[a] > 1 and block[a] % tiles[a] == 0:
                    start = pl.multiple_of(start, tiles[a])
                idx.append(pl.ds(start, length))
            out.append(pltpu.make_async_copy(
                in_refs[f.array].at[tuple(idx)], bufs[n].at[slot],
                sem.at[slot, n]))
        return out

    def step(refs, linear, corner, scope):
        n_in = len(array_names)
        in_refs = dict(zip(array_names, refs[:n_in]))
        out_ref = refs[n_in]
        bufs, sem = refs[n_in + 1:-1], refs[-1]

        def start(t):
            for cp in copies(in_refs, bufs, sem, unravel(t),
                             lax.rem(t, depth)):
                cp.start()

        with scope(issue_region):
            # step 0 also starts the steps before its own look-ahead
            @pl.when(linear == 0)
            def _():
                for t in range(min(depth - 1, n_steps)):
                    start(jnp.int32(t))

            # the step depth - 1 ahead, into the slot this step's
            # predecessor has finished reading; the last depth - 1
            # steps start nothing, so no copy is in flight at the end
            ahead = linear + (depth - 1)

            @pl.when(ahead < n_steps)
            def _():
                start(ahead)

        slot = lax.rem(linear, depth)
        with scope(wait_region):
            for cp in copies(in_refs, bufs, sem, corner, slot):
                cp.wait()

        # tap offsets -> loaded values
        tap_val: Dict[Tuple[str, Tuple[int, ...]], jnp.ndarray] = {}
        for n, (f, win) in enumerate(zip(plan.fetches, windows)):
            for off in f.taps:
                sl = [slot]
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
        linear = sum(pl.program_id(a) * strides[a] for a in range(nd))
        corner = [pl.program_id(a) * block[a] for a in range(nd)]
        if not trace_every:
            step(refs, linear, corner, _no_scope)
            return
        # grid steps whose linear index is a multiple of trace_every
        # record REGIONS; the others run the same ring unrecorded
        sampled = linear % trace_every == 0
        pl.when(sampled)(lambda: step(refs, linear, corner,
                                      jax.named_scope))
        pl.when(jnp.logical_not(sampled))(
            lambda: step(refs, linear, corner, _no_scope))

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

    The grid runs in order, its fetches in a ring of
    :func:`ring_depth` slots: each step starts the block ``depth - 1``
    steps ahead (step 0 also those before it), waits for its own slot
    and computes, so no step waits for a DMA started in that step, and
    none is in flight when the kernel ends.

    With ``trace_every`` N > 0, grid steps whose linear index is a
    multiple of N record the named trace regions :data:`REGIONS` (the
    look-ahead's DMA issue, the wait for this step's slot, compute),
    which a device profile shows where the compiler is asked for
    custom-call region traces; 0 builds the kernel without any trace op.
    """
    assert mode in MODES
    assert trace_every >= 0
    block = tuple(block) if block else DEFAULT_BLOCKS[prog.ndim]
    assert len(block) == prog.ndim
    plan = make_plan(prog, mode)
    scalars = dict(scalars or {})
    array_names = sorted(a for a in prog.arrays if a != prog.out.array)
    nd = prog.ndim
    out_dtype = jnp.dtype(jnp.float32)

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
        grid = tuple(interior[a] // block[a] for a in range(nd))
        depth = ring_depth(plan.bytes_per_block(block, itemsize),
                           out_dtype.itemsize * math.prod(block))
        kernel, windows = _build_kernel(prog, plan, block, grid, depth,
                                        scalars, array_names, itemsize,
                                        trace_every)
        scratch = [pltpu.VMEM((depth,) + tuple(n for _, n in win),
                              first.dtype)
                   for win in windows]
        scratch.append(pltpu.SemaphoreType.DMA((depth, len(windows))))
        fn = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)
                      for _ in array_names],
            out_specs=pl.BlockSpec(block, lambda *p: p),
            out_shape=jax.ShapeDtypeStruct(interior, out_dtype),
            scratch_shapes=scratch,
            # the ring carries fetches from step to step, so the grid
            # runs in order, on one core
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * nd),
            interpret=interpret,
        )
        return fn(*[arrays[a] for a in array_names])

    return apply_fn
