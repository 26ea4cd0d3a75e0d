"""The stencil kernel's trace regions and the program's compile counter,
as the benchmark reads them: shares from made-up region events, and the
new metrics in a traced rehearsal on the CPU."""

import contextlib

import jax
import pytest

import small
from harness import regions, spec

DMA_ISSUE, DMA_WAIT, COMPUTE = regions.REGIONS
NEW = {"stencil_dma_wait_share", "stencil_step_overhead_share",
       "setup_compile_s", "window_compiles"}


def test_shares_and_overhead_add_up_to_100():
    got = regions.shares({DMA_ISSUE: [10, 10], DMA_WAIT: [50, 50],
                          COMPUTE: [20, 20]}, kernel=[1000], steps=10)
    assert got == {DMA_ISSUE: pytest.approx(10.0),
                   DMA_WAIT: pytest.approx(50.0),
                   COMPUTE: pytest.approx(20.0),
                   regions.OVERHEAD: pytest.approx(20.0)}


def test_a_sampled_pass():
    """Three sweeps of 1,000 steps of 100 ns, of which issue 7, wait 60
    and compute 13; every 251st step is sampled (4 a sweep), and the
    sampled steps vary around those means."""
    steps, sweeps = 1000, 3
    sampled = list(range(0, steps, regions.TRACE_EVERY)) * sweeps
    jitter = [(-2, 2, -1, 1)[i % 4] for i in range(len(sampled))]
    events = {DMA_ISSUE: [7 + j for j in jitter],
              DMA_WAIT: [60 - j for j in jitter],
              COMPUTE: [13 for _ in sampled]}
    got = regions.shares(events, kernel=[100 * steps] * sweeps, steps=steps)
    assert got[DMA_ISSUE] == pytest.approx(7.0)
    assert got[DMA_WAIT] == pytest.approx(60.0)
    assert got[COMPUTE] == pytest.approx(13.0)
    assert got[regions.OVERHEAD] == pytest.approx(20.0)
    assert sum(got.values()) == pytest.approx(100.0)


@pytest.mark.parametrize("missing", [DMA_ISSUE, DMA_WAIT, COMPUTE, "kernel"])
def test_no_shares_without_every_region_and_the_kernel(missing):
    events = {r: [1] for r in regions.REGIONS if r != missing}
    kernel = [] if missing == "kernel" else [100]
    assert regions.shares(events, kernel, steps=10) is None


@pytest.mark.parametrize("cell,steps", [("kernelgen-e5.tricubic", 258_048),
                                        ("kernelgen-e5.jacobi", 262_144)])
def test_grid_steps_of_the_cells(cell, steps):
    from repro.core.frontend.kernelgen import get_bench
    from repro.kernels.stencil import DEFAULT_BLOCKS
    c = spec.find_cell(cell)
    prog = get_bench(c.traffic["program"]).program
    shape = c.config[f"grid_{prog.ndim}d"]
    assert regions.grid_steps(shape, prog.halo[0],
                              DEFAULT_BLOCKS[prog.ndim]) == steps


# ---------------------------------------------------------------------------
# the traced rehearsal
# ---------------------------------------------------------------------------

@pytest.fixture
def counter(monkeypatch):
    """A compile counter of the test's own, registered before the
    rehearsal's set-up and taken away after it."""
    from repro.runtime import compile_cache
    c = compile_cache.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(c.on_duration)
    jax.monitoring.register_event_listener(c.on_event)
    monkeypatch.setattr(compile_cache, "_COUNTER", c)
    yield c
    jax.monitoring.unregister_event_duration_listener(c.on_duration)
    jax.monitoring.unregister_event_listener(c.on_event)


def _traced(monkeypatch, cell="kernelgen-e5.jacobi"):
    """A traced rehearsal; the CPU has no device plane, so the recorded
    chip trace stands in for the window's profile."""
    from harness import trace as trace_mod
    import test_trace

    class Canned:
        def result(self, kernel_names=None):
            t = test_trace.recorded()
            t.kernel_names = tuple(kernel_names)
            return t

    monkeypatch.setattr(trace_mod, "capture",
                        contextlib.contextmanager(lambda on: (yield Canned())))
    return small.run_small(cell, trace=True)


def test_traced_rehearsal_reports_the_compile_counter(monkeypatch, counter):
    r = _traced(monkeypatch)
    got = set(r["metrics"])
    # the CPU runs kernels in the interpreter, which records no regions
    assert got & NEW == {"setup_compile_s", "window_compiles"}
    assert r["metrics"]["window_compiles"]["value"] == 0
    assert r["metrics"]["setup_compile_s"]["value"] > 0
    assert r["metrics"]["setup_compile_s"]["unit"] == "s"


def test_program_without_the_counter_reports_none_of_it(monkeypatch):
    """A program that lacks the counter (or a run that never registered
    it) leaves its metrics out, and the run goes on."""
    from repro.runtime import compile_cache
    monkeypatch.delattr(compile_cache, "snapshot")
    r = _traced(monkeypatch)
    assert r["correct"] is True
    assert not set(r["metrics"]) & NEW
    assert {"stencil_roofline", "device_idle.stencil"} <= set(r["metrics"])
