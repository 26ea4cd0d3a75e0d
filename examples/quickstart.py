"""Quickstart: the paper's full pipeline on the Jacobi kernel.

1. Write the OpenACC-style loop nest (Listing 4) in the stencil DSL.
2. Lower to the PTX subset (what NVHPC would emit).
3. PTXASW: symbolic emulation -> memory trace -> shuffle detection
   (finds the paper's 6/9 shuffles, mean delta 1.5, and the worked
   N = -2 example) -> shfl.sync synthesis (Listing 6).
4. Validate bit-exact equivalence on the concrete 32-lane warp
   emulator, incomplete final warp included.
5. Cycle-model speedups per GPU generation (Figure 2 structure).
6. The TPU port: the same detection drives a Pallas kernel whose taps
   are shifted slices of one staged VMEM tile; report HBM traffic of
   naive vs paper vs tile plans.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core.frontend.stencil import Array, I, J, Program, Scalar, lower_to_ptx
from repro.core.ptx import print_kernel
from repro.core.driver import Compiler
from repro.core.emulator.concrete import run_concrete
from repro.core.emulator.cycles import speedup_table
from repro.core.frontend.pallas_lower import synthesize_tpu
from repro.kernels.stencil import stencil_apply, reference, traffic_report
import jax.numpy as jnp


def main():
    # -- 1. the kernel (paper Listing 4) --------------------------------
    w0 = Array("w0")
    c0, c1, c2 = Scalar("c0"), Scalar("c1"), Scalar("c2")
    expr = (c0 * w0[I(), J()]
            + c1 * (w0[I(-1), J()] + w0[I(), J(-1)]
                    + w0[I(1), J()] + w0[I(), J(1)])
            + c2 * (w0[I(-1), J(-1)] + w0[I(-1), J(1)]
                    + w0[I(1), J(-1)] + w0[I(1), J(1)]))
    prog = Program(name="jacobi", ndim=2, out=Array("w1")[I(), J()],
                   expr=expr, scalars=["c0", "c1", "c2"], lang="F")

    # -- 2-3. PTXASW through the driver facade ----------------------------
    # one Compiler session owns options, a session-scoped result cache,
    # and the worker pool; it ingests the DSL program directly (the
    # stencil frontend lowers it) and returns a structured CompileResult
    compiler = Compiler()
    kernel = lower_to_ptx(prog)
    result = compiler.compile(prog)
    synthesized, report = result.module.kernels[0], result.reports[0]
    print("== detection ==")
    print(report.summary)
    print("  passes:", " -> ".join(f"{n} {t * 1e3:.1f}ms"
                                   for n, t in result.pass_times.items()))
    again = compiler.compile(kernel)   # same PTX via a different frontend
    assert again.cached, "second compile should hit the session cache"
    assert again.ptx == result.ptx, "frontends must normalize identically"
    print(f"  recompile: served from the session cache "
          f"({compiler.cache_stats.summary})")
    for p in report.detection.pairs:
        print(f"  load@{p.dst_uid} covered by load@{p.src_uid} "
              f"shfl delta N={p.delta}")
    print("\n== synthesized PTX (excerpt) ==")
    text = print_kernel(synthesized)
    shfl_lines = [l for l in text.splitlines() if "shfl" in l or "activemask" in l]
    print("\n".join(shfl_lines[:6]))

    # -- 4. bit-exact validation on the warp emulator ---------------------
    rng = np.random.default_rng(0)
    ny, nx = 6, 70                       # interior 68: incomplete last warp
    w0a = rng.standard_normal((ny, nx)).astype(np.float32)
    import struct
    cbits = lambda v: int(np.frombuffer(np.float32(v).tobytes(), np.uint32)[0])
    def run(k):
        out = np.zeros((ny, nx), np.float32)
        params = {"w0": w0a.copy(), "w1": out, "n0": nx, "n1": ny,
                  "c0": cbits(.5), "c1": cbits(.25), "c2": cbits(.125)}
        stats = run_concrete(k, params, ntid=(64, 1, 1),
                             nctaid=(-(-68 // 64), ny - 2, 1))
        return out, stats
    o1, s1 = run(kernel)
    o2, s2 = run(synthesized)
    assert np.array_equal(o1, o2), "synthesized code changed results!"
    print(f"\n== concrete validation == bit-exact; "
          f"loads {s1.get('load_global')} -> {s2.get('load_global')} "
          f"(+{s2.get('shfl')} shuffles, {s2.get('corner_load')} corner loads)")

    # -- 5. cycle model ----------------------------------------------------
    versions = {"original": s1, "ptxasw": s2}
    table = speedup_table(versions)
    print("\n== cycle model (speedup vs original) ==")
    for arch, row in table.items():
        print(f"  {arch:<8} ptxasw {row['ptxasw']:.3f}x")

    # -- 6. TPU port --------------------------------------------------------
    plan = synthesize_tpu(prog)
    assert plan.consistent
    arrays = {"w0": jnp.asarray(rng.standard_normal((20, 140)), jnp.float32)}
    scal = {"c0": .5, "c1": .25, "c2": .125}
    ref = reference(prog, arrays, scal)
    for mode in ("naive", "paper", "tile"):
        out = stencil_apply(prog, arrays, scal, mode=mode, block=(8, 32),
                            interpret=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
    t = traffic_report(prog, (32768, 32768))
    print("\n== TPU Pallas port (32768x32768) ==")
    print(f"  HBM reads: naive {t['naive']:.3e} B -> paper "
          f"{t['paper']:.3e} B ({t['reduction_paper']:.2f}x) -> tile "
          f"{t['tile']:.3e} B ({t['reduction_tile']:.2f}x)")
    print("\nquickstart OK")


if __name__ == "__main__":
    main()
