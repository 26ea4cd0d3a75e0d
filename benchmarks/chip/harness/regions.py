"""The Pallas stencil kernel's trace regions, read from a short region
pass after a traced run's window.

A kernel built with ``trace_every`` N > 0 (``stencil_apply``) records
three named regions in every grid step whose linear index is a multiple
of N, once the compiler is asked for custom-call region traces
(``OPTIONS``, given to that one executable).  After the window the pass
makes the cell's inputs again from the seed, builds that executable
beside the plain one, runs one sweep of it under the profiler, and
checks that its output equals the plain kernel's bit for bit.  One
sweep is enough (up to about a thousand sampled steps; a full profile
may drop some) and all a pass can afford: the compile option also puts
several events per grid step on the device's ``Tensor Core`` line, and
collecting a sweep's two million takes about 40 s on a v5e.  The
window, its executable and the process's flags stay as they were, so
every other metric reads what it read without the pass.

A region's share is its mean duration per sampled step, times the grid's
steps, over the kernel's custom-call time per sweep of the pass; what
the three leave is the step overhead: the grid loop, the output block's
writeback, and whatever else runs between step bodies.
"""

from __future__ import annotations

import functools
import glob
import inspect
import math
import os
import sys
import tempfile
import time
import weakref
from typing import Dict, List, Optional, Sequence

from . import compiles, counts
from .trace import DEVICE_PREFIX, OPS_LINE

REGIONS = ("stencil_dma_issue", "stencil_dma_wait", "stencil_compute")
OVERHEAD = "overhead"
# a prime: the sampled steps fall on every row and column of the grid,
# about a thousand a sweep in both cells (3 regions each)
TRACE_EVERY = 251
OPTIONS = {"xla_enable_custom_call_region_trace": True}
# lines of the device plane that hold XLA's own events, not regions
XLA_LINES = (OPS_LINE, "XLA Modules", "Async XLA Ops")

_READINGS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def grid_steps(shape: Sequence[int], halo: int,
               block: Sequence[int]) -> int:
    """The kernel's grid steps in one sweep: the interior padded up to
    whole blocks, in blocks."""
    interior = counts.stencil_interior(shape, halo)
    return math.prod(-(-n // b) for n, b in zip(interior, block))


def read_regions(path: str, kernel_names: Sequence[str]) -> Dict:
    """From a profile: every region event's duration (ns) by region, the
    kernel's custom-call durations, and the names of the device lines
    the regions were found on."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    regions: Dict[str, List[int]] = {r: [] for r in REGIONS}
    kernel: List[int] = []
    lines = set()
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if line.name == OPS_LINE:
                    if any(k in e.name for k in kernel_names):
                        kernel.append(int(e.duration_ns))
                    continue
                if line.name in XLA_LINES:
                    continue
                for r in REGIONS:
                    if r in e.name:
                        regions[r].append(int(e.duration_ns))
                        lines.add(line.name)
    return {"regions": regions, "kernel": kernel, "lines": sorted(lines)}


def shares(regions: Dict[str, List[int]], kernel: List[int],
           steps: int) -> Optional[Dict[str, float]]:
    """Each region's share of the kernel's time, in %, and the rest as
    ``OVERHEAD``; None where a region or the kernel was not seen."""
    if not kernel or any(not regions.get(r) for r in REGIONS):
        return None
    per_sweep = sum(kernel) / len(kernel)
    out = {r: 100.0 * steps * (sum(regions[r]) / len(regions[r]))
           / per_sweep for r in REGIONS}
    out[OVERHEAD] = 100.0 - sum(out.values())
    return out


def readings(ctx, facts) -> Optional[Dict]:
    """The region pass's result for this traced run (run once, on the
    first call), or None where the platform or the program records no
    regions."""
    if ctx.trace is None:
        return None
    if ctx.trace not in _READINGS:
        compiles.reading(ctx)         # before the pass compiles anything
        _READINGS[ctx.trace] = {"pass": _region_pass(ctx, facts)}
    return _READINGS[ctx.trace]["pass"]


def _region_pass(ctx, facts) -> Optional[Dict]:
    # Mosaic's trace regions exist only in kernels compiled for the TPU
    if ctx.platform != "tpu" or "min_bytes_per_sweep" not in facts:
        return None
    from repro.kernels.stencil import DEFAULT_BLOCKS, stencil_apply
    if "trace_every" not in inspect.signature(stencil_apply).parameters:
        return None
    import jax
    import numpy as np
    from repro.core.driver import Compiler
    from repro.core.frontend.kernelgen import get_bench
    from repro.core.frontend.pallas_lower import synthesize_tpu

    t0 = time.perf_counter()
    bench = get_bench(ctx.traffic["program"])
    prog = bench.program
    mode = synthesize_tpu(prog, max_delta=bench.max_delta,
                          compiler=Compiler()).plan.mode
    shape = tuple(ctx.config[f"grid_{prog.ndim}d"])
    names = sorted(a for a in prog.arrays if a != prog.out.array)
    arrays = ctx.cell.driver().make_inputs(ctx.seed, names, shape)
    apply = functools.partial(stencil_apply, prog,
                              scalars=dict(ctx.traffic.get("scalars", {})),
                              mode=mode)
    want = np.asarray(jax.jit(apply)(arrays))
    traced = jax.jit(functools.partial(apply, trace_every=TRACE_EVERY)) \
        .lower(arrays).compile(compiler_options=OPTIONS)
    traced(arrays).block_until_ready()
    t1 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="bench-regions-") as d:
        jax.profiler.start_trace(d)
        try:
            out = traced(arrays)
            out.block_until_ready()
        finally:
            jax.profiler.stop_trace()
        t2 = time.perf_counter()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError("the region pass's profiler wrote no trace")
        got = read_regions(found[0], facts["kernel_names"])
    if not np.array_equal(np.asarray(out), want):
        raise RuntimeError("the region-traced kernel's output differs from "
                           "the plain kernel's")
    del arrays, out, want
    steps = grid_steps(shape, prog.halo[0], DEFAULT_BLOCKS[prog.ndim])
    got["shares"] = shares(got["regions"], got["kernel"], steps)
    window_ms = 1e3 * ctx.trace.kernel_s() / max(facts["units"], 1)
    pass_ms = 1e-6 * sum(got["kernel"]) / max(len(got["kernel"]), 1)
    print(f"[regions] lines {got['lines']}; {steps} steps, "
          f"{len(got['regions']['stencil_dma_wait'])} sampled; kernel "
          f"{pass_ms!r} ms a sweep against {window_ms!r} in the window; "
          f"shares {got['shares']}; pass {time.perf_counter() - t0!r} s "
          f"wall, {t2 - t1!r} s profiled", file=sys.stderr)
    return got
