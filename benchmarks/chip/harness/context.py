"""What a driver and a metric reader are given about the run."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from .spec import Cell


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    devices: Any
    trace: Optional[Any] = None     # harness.trace.Trace of a traced run

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
