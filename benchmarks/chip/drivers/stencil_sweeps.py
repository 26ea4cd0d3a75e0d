"""Driver for stencil traffic: repeated sweeps of one E5 program.

Set-up builds the plan as a compiler user would, ``synthesize_tpu``
(PTXASW detection through a ``Compiler`` session), and runs
``stencil_apply`` with the plan that call returns; the driver never
chooses a fetch mode itself.  Each timed unit is one call of the jitted
``stencil_apply`` over the seeded inputs, waited for.  The last sweep's
output is compared with the plain reference after the window.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import counts
from harness.context import seed_key
from harness.trace import span


def make_inputs(seed: int, names, shape):
    """The program's input arrays, standard normal f32, made on the
    device in one jitted call from the seed."""
    keys = jax.random.split(seed_key(seed), len(names))

    @jax.jit
    def make(keys):
        return {n: jax.random.normal(keys[i], shape, jnp.float32)
                for i, n in enumerate(names)}

    return make(keys)


class Driver:
    def __init__(self, ctx):
        from repro.core.driver import Compiler
        from repro.core.frontend.kernelgen import get_bench
        from repro.core.frontend.pallas_lower import synthesize_tpu
        from repro.kernels.stencil import stencil_apply

        self.ctx = ctx
        traffic, config = ctx.traffic, ctx.config
        self.name = traffic["program"]
        bench = get_bench(self.name)
        prog = bench.program
        with ctx.part("detect"):
            self.tpu_plan = synthesize_tpu(prog, max_delta=bench.max_delta,
                                           compiler=Compiler())
        if not self.tpu_plan.consistent:
            raise RuntimeError(f"{self.name}: detection and plan disagree")
        self.plan = self.tpu_plan.plan
        self.shape = tuple(config[f"grid_{prog.ndim}d"])
        self.halo = prog.halo[0]
        self.input_names = sorted(a for a in prog.arrays
                                  if a != prog.out.array)
        self.scalars = dict(traffic.get("scalars", {}))
        with ctx.part("inputs"):
            self.arrays = make_inputs(ctx.seed, self.input_names, self.shape)
        self.fn = jax.jit(functools.partial(
            stencil_apply, prog, scalars=self.scalars, mode=self.plan.mode,
            interpret=ctx.interpret))
        with ctx.part("warmup"):
            self.out = self.fn(self.arrays)
            self.out.block_until_ready()
        self.sweeps = 0

    def begin(self):
        self.sweeps = 0

    def unit(self):
        self.out = None
        with span("sweep"):
            out = self.fn(self.arrays)
            out.block_until_ready()
        self.out = out
        self.sweeps += 1

    def facts(self, window_s: float):
        from repro.kernels.stencil import DEFAULT_BLOCKS
        points = counts.stencil_points(self.shape, self.halo)
        block = DEFAULT_BLOCKS[len(self.shape)]
        interior = counts.stencil_interior(self.shape, self.halo)
        n_blocks = math.prod(-(-n // b) for n, b in zip(interior, block))
        return {
            "attempted": self.sweeps,
            "checked": 1,
            "units": self.sweeps,
            "end_to_end": {"stencil_gpts_s":
                           points * self.sweeps / window_s / 1e9},
            "min_bytes_per_sweep": counts.stencil_min_bytes(
                self.shape, self.halo, len(self.input_names)),
            # the program's own count of what the chosen plan's DMAs copy
            "plan_bytes_per_sweep": self.plan.bytes_per_block(block)
            * n_blocks,
            # the Pallas kernel's custom call in the device trace
            "kernel_names": ['custom_call_target="tpu_custom_call"'],
            "window_s": window_s,
        }

    def release(self):
        self.fn = None

    def _reference(self, dtype=jnp.float32):
        ref = self.ctx.reference()
        return jax.jit(functools.partial(ref.evaluate, self.name,
                                         scalars=self.scalars, dtype=dtype))(
            self.arrays)

    def checks(self):
        return {"max_rel_err": {
            "value": max_rel_err(self.out, self._reference()),
            "limit": self.ctx.traffic["limits"]["max_rel_err"]}}

    def control(self):
        """The reference in bfloat16, the precision below the
        configuration's float32, in the program's place."""
        return {"control": {"max_rel_err": max_rel_err(
            self._reference(jnp.bfloat16), self._reference())}}


@jax.jit
def _errs(out, want):
    return (jnp.max(jnp.abs(out.astype(jnp.float32) - want)),
            jnp.max(jnp.abs(want)))


def max_rel_err(out, want) -> float:
    """max|out - want| / max|want|; infinite where the shapes differ or
    the output is not finite."""
    if out is None or out.shape != want.shape:
        return float("inf")
    err, scale = (float(x) for x in _errs(out, want))
    if not math.isfinite(err) or scale == 0:
        return float("inf")
    return err / scale
