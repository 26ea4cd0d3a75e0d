"""E5 — TPU-port benchmark: Pallas stencil HBM traffic, naive vs
shuffle-synthesized plans (beyond-paper deliverable).

For each stencil benchmark: analytic HBM read bytes for the three fetch
plans (naive = paper Original, paper = PTXASW row reuse, tile = TPU
2D/3D halo tile), interpret-mode wall time on a small grid as a
correctness-weighted sanity check, and the conv1d kernel's traffic for
the Mamba-2 integration.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.frontend.kernelgen import get_bench
from repro.core.frontend.pallas_lower import synthesize_tpu
from repro.kernels.stencil import reference, stencil_apply, traffic_report
from repro.kernels.conv1d import hbm_bytes as conv_bytes

from .common import emit, session, timed

BENCHES = ("jacobi", "gaussblur", "tricubic", "lapgsrb", "wave13pt")
FULL_SHAPES = {2: (32768, 32768), 3: (512, 1024, 1024)}   # paper's sizes


def run() -> bool:
    ok = True
    rng = np.random.default_rng(0)
    for name in BENCHES:
        b = get_bench(name)
        prog = b.program
        nd = prog.ndim
        # detection via the harness session's cached analysis pipeline;
        # a repeated plan request for the same program — the serving
        # path — must be cache-served with zero re-emulation
        cc = session()
        plan = synthesize_tpu(prog, max_delta=b.max_delta, compiler=cc)
        hits_before = cc.cache_stats.hits
        plan2 = synthesize_tpu(prog, max_delta=b.max_delta, compiler=cc)
        ok &= plan.consistent and plan2.consistent
        ok &= cc.cache_stats.hits == hits_before + 1
        emit(f"pallas.{name}.shuffles", plan.n_shuffles, "count",
             "detection drives the VMEM row plan")
        t = traffic_report(prog, FULL_SHAPES[nd])
        emit(f"pallas.{name}.hbm_naive", t["naive"], "bytes",
             "one fetch per static load (paper Original)")
        emit(f"pallas.{name}.hbm_paper", t["paper"], "bytes",
             "PTXASW row reuse")
        emit(f"pallas.{name}.hbm_tile", t["tile"], "bytes",
             "TPU halo tile (beyond paper)")
        emit(f"pallas.{name}.reduction_paper", t["reduction_paper"], "x")
        emit(f"pallas.{name}.reduction_tile", t["reduction_tile"], "x")
        ok &= t["reduction_tile"] >= t["reduction_paper"] >= 0.99
        # correctness spot check on a small grid (interpret mode)
        small = {2: (20, 140), 3: (6, 20, 140)}[nd]
        arrays = {a: jnp.asarray(rng.standard_normal(small[-dim:]),
                                 jnp.float32)
                  for a, dim in prog.arrays.items() if a != prog.out.array}
        scalars = {s: 0.3 for s in prog.scalars}
        ref = reference(prog, arrays, scalars)
        for mode in ("naive", "paper", "tile"):
            out, dt = timed(stencil_apply, prog, arrays, scalars, mode=mode,
                            block={2: (8, 32), 3: (1, 8, 32)}[nd],
                            interpret=True, repeat=1)
            err = float(jnp.max(jnp.abs(out - ref)))
            ok &= err < 1e-3
            emit(f"pallas.{name}.{mode}.interpret_s", dt, "s",
                 f"maxerr={err:.1e}")
    # conv1d (Mamba-2 integration)
    r = conv_bytes(4096, 4096 + 2 * 128, 4, "naive") / \
        conv_bytes(4096, 4096 + 2 * 128, 4, "shuffle")
    emit("pallas.conv1d.reduction", r, "x",
         "W=4 causal conv: one halo fetch vs 4 tap fetches")
    ok &= r > 3.5
    stats = session().cache_stats
    emit("pallas.compile_cache.hits", stats.hits, "count")
    emit("pallas.compile_cache.misses", stats.misses, "count")
    emit("pallas.compile_cache.hit_rate", stats.hit_rate, "x")
    emit("pallas.STRUCTURE_OK", int(ok), "bool")
    return ok
