"""Run a cell of the benchmark at a tiny size on the CPU, skipping the
harness's look for a chip: the rehearsal the CPU tests build on."""

from __future__ import annotations

import copy
import os
import sys
import time
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, "..", "..", "src"))

import run  # noqa: E402
from harness import peaks, spec  # noqa: E402

# sizes that a test run holds; every other key is the committed one
SMALL_CONFIG = {
    "kernelgen-e5": {"grid_2d": [18, 260], "grid_3d": [6, 12, 136]},
    "olmo-1b": {"hidden_size": 64, "intermediate_size": 128,
                "num_hidden_layers": 2, "num_attention_heads": 4,
                "num_key_value_heads": 4, "vocab_size": 256},
}
SMALL_TRAFFIC = {
    "train": {"seq_len": 32},
    "decode": {"batch": 3, "prompt_len": 12, "gen": 6},
}
# the CPU stands in for a chip in the metric readers' arithmetic only
CPU_PEAKS = dict(peaks.PEAKS["TPU v5 lite"])


def small_cell(name, config: Optional[Dict[str, Any]] = None,
               traffic: Optional[Dict[str, Any]] = None) -> spec.Cell:
    cell = copy.deepcopy(name if isinstance(name, spec.Cell)
                         else spec.find_cell(name))
    cell.config.update(SMALL_CONFIG.get(cell.config_name, {}))
    cell.traffic.update(SMALL_TRAFFIC.get(cell.traffic_name, {}))
    cell.config.update(config or {})
    cell.traffic.update(traffic or {})
    return cell


def run_cell_small(cell: spec.Cell, seconds: float = 0.5,
                   trace: bool = False, seed: int = 2**31 + 11,
                   **overrides) -> Dict[str, Any]:
    """One run of ``cell`` at the small sizes, on the CPU, quietly."""
    import jax
    devices = jax.devices()
    peaks.PEAKS.setdefault(devices[0].device_kind, CPU_PEAKS)
    return run.run_cell(small_cell(cell, **overrides), seed, seconds, trace,
                        devices, time.perf_counter(),
                        log=lambda *a, **k: None)


def run_small(name: str, **kw) -> Dict[str, Any]:
    return run_cell_small(spec.find_cell(name), **kw)
