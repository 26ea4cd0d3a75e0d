"""Run one cell of the benchmark once, on the chip it is started on.

    python3 benchmarks/chip/run.py --workload kernelgen-e5.tricubic \
        --seed 7 --seconds 10 --trace 0

The cell's configuration, traffic mix, driver and per-layer metric
readers are found by name (``harness/spec.py``).  A run:

1. refuses to start without a TPU, or with fewer chips than the cell asks;
2. builds and warms up everything the cell's traffic uses (``setup_s``,
   from process start to the first timed unit);
3. drives timed units back to back for ``--seconds``; when the time is
   up it starts no more, waits for all it started (a driver that keeps
   work queued ahead waits in its ``finish``), and reads the clock: the
   window ends when the last unit that the window started is done;
4. reads the device's peak memory, frees the program's state, and
   compares what the window produced with the plain reference;
5. prints the compared numbers beside their limits on standard error,
   and as the last line of standard output one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
   ``--trace 1`` the per-layer ones), ``device``, with ``--trace 1``
   ``breakdown``, and last ``checks``.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(_HERE, "..", "..", "src"))

from harness import spec as spec_mod  # noqa: E402
from harness.context import RunContext  # noqa: E402


class NoChip(RuntimeError):
    """The machine has no TPU, or fewer chips than the cell asks for."""


def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), written for
    every compile however short, so that only a cell's first run in a
    checkout compiles."""
    import jax
    from repro.runtime import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices, peak: Optional[int]) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def unit_summary(unit_s: List[float]) -> str:
    """The window's units by host time: their count, median and longest,
    and the seconds that units over twice the median took beyond it (a
    stall of the host or the device shows there)."""
    ordered = sorted(unit_s)
    median = ordered[len(ordered) // 2]
    slow = [u for u in unit_s if u > 2 * median]
    return (f"units {len(unit_s)}, median {median:.6f} s, longest "
            f"{ordered[-1]:.6f} s, {len(slow)} over twice the median "
            f"losing {sum(u - median for u in slow):.6f} s")


def finish(drv) -> None:
    """Waits for the work a driver keeps queued ahead, where it keeps any."""
    if hasattr(drv, "finish"):
        drv.finish()


def run_cell(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, log=print) -> Dict[str, Any]:
    """Set up, time, check and report one run; returns the result line."""
    from harness import trace as trace_mod

    ctx = RunContext(cell=cell, seed=seed, devices=devices)
    ctx.setup_parts["start"] = time.perf_counter() - t_start
    drv = cell.driver().Driver(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"[bench] {cell.name}: set-up {setup_s:.3f} s", file=sys.stderr)
    log("[setup] " + ", ".join(f"{k} {v:.3f} s" for k, v in
                               ctx.setup_parts.items()), file=sys.stderr)

    with trace_mod.capture(trace) as captured:
        drv.begin()
        with trace_mod.span("window"):
            w0 = time.perf_counter()
            t = w0
            unit_s = []
            while t - w0 < seconds:
                drv.unit()
                unit_s.append(time.perf_counter() - t)
                t = time.perf_counter()
            finish(drv)
            t = time.perf_counter()
    window_s = t - w0
    log(f"[window] {unit_summary(unit_s)}", file=sys.stderr)
    peak = memory_peak(devices)
    facts = drv.facts(window_s)

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        ctx.trace = captured.result(kernel_names=facts.get("kernel_names"))
        for m in cell.per_layer:
            value = spec_mod.metric_reader(cell, m["name"]).read(
                ctx, facts, ctx.trace)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        e2e = dict(facts.get("end_to_end", {}))
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise KeyError(f"{cell.name} reports no {m['name']}")
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    drv.release()
    t_check = time.perf_counter()
    checks = drv.checks()
    log(f"[bench] {cell.name}: reference check "
        f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and bool(checks)
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
            file=sys.stderr)

    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": facts["attempted"],
        # what the check covered is wrong: that many units failed
        "failed": 0 if correct else facts["checked"],
        "metrics": metrics,
        "device": device_info(devices, peak),
    }
    if trace:
        result["device"]["busy_s"] = ctx.trace.busy_s()
        result["device"]["window_s"] = ctx.trace.window_s()
        result["breakdown"] = ctx.trace.breakdown()
    result["checks"] = checks
    return result


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    cell = spec_mod.find_cell(args.workload)
    try:
        devices = check_devices(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    enable_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, _T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
