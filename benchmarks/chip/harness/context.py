"""What a driver and a metric reader are given about the run."""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, Optional

from .spec import Cell


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    devices: Any
    trace: Optional[Any] = None     # harness.trace.Trace of a traced run
    # seconds of the driver's set-up by part, logged by run.py
    setup_parts: Dict[str, float] = dataclasses.field(default_factory=dict)

    @contextlib.contextmanager
    def part(self, name: str):
        """A part of set-up: a host span, and its seconds added to
        ``setup_parts[name]``."""
        from .trace import span
        t0 = time.perf_counter()
        with span(name):
            yield
        self.setup_parts[name] = (self.setup_parts.get(name, 0.0)
                                  + time.perf_counter() - t0)

    @property
    def config(self):
        return self.cell.config

    @property
    def traffic(self):
        return self.cell.traffic

    @property
    def platform(self) -> str:
        return self.devices[0].platform

    @property
    def interpret(self) -> bool:
        """Pallas kernels run compiled on the TPU; only a rehearsal off
        the chip (the CPU tests) runs them in the interpreter."""
        return self.platform != "tpu"

    def peaks(self):
        from .peaks import peaks_for
        return peaks_for(self.devices[0].device_kind)

    def reference(self):
        return self.cell.reference()


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, 64-bit ones included."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
