"""Readings that set the limits of ``correct``: the program's and the
control's, seed by seed, at the cell's own size, on the chip.

    python3 benchmarks/chip/control.py --workload olmo-1b.train \
        --seeds 11 12 13 --seconds 3

For each seed, in one process: the cell's set-up, a short window at its
own load, the compared numbers of the program (as a run reports them),
and the same numbers with the control in the program's place (the
reference in the precision below the configuration's), and for
training with a planted fault (half of each batch's tokens left out).
Each side is judged against the cell's limits as a run would judge it
(``correct``); a reading without a limit is reported and not judged.
One JSON line per seed, each seed in a process of its own: three train
references in a row leave more float32 host arrays behind than a 40 GiB
host holds.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(_HERE, "..", "..", "src"))

from harness import spec as spec_mod  # noqa: E402
from harness.context import RunContext  # noqa: E402


def readings(cell, seed: int, seconds: float, devices):
    import run
    ctx = RunContext(cell=cell, seed=seed, devices=devices)
    drv = cell.driver().Driver(ctx)
    drv.begin()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        drv.unit()
    run.finish(drv)
    drv.release()
    out = drv.control()
    if "program" not in out:
        out["program"] = {k: c["value"] for k, c in drv.checks().items()}
    limits = cell.traffic["limits"]
    correct = {side: all(v <= limits[k] for k, v in nums.items()
                         if k in limits)
               for side, nums in out.items() if side != "losses"}
    return {"seed": seed, **out, "correct": correct}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    if len(args.seeds) > 1:
        # a child a seed; this process never touches the chip
        rcs = [subprocess.call([sys.executable, os.path.abspath(__file__),
                                "--workload", args.workload,
                                "--seeds", str(seed),
                                "--seconds", str(args.seconds)])
               for seed in args.seeds]
        return max(rcs)
    import run
    cell = spec_mod.find_cell(args.workload)
    try:
        devices = run.check_devices(cell.chips)
    except run.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    run.enable_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds, devices)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
