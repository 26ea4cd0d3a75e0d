"""The program's compile counter (``repro.runtime.compile_cache``), read
once per traced run, right after the window.

The counter sums JAX's compile events from its registration, which
``enable_compile_cache`` makes before the cell's set-up, and keeps apart
the events that fired while a profiler trace ran: in a traced run, the
window's.  The one reading is taken before any reader compiles anything
of its own (the region pass, ``harness.regions``, takes it first too),
so the set-up's share is the sum less the profiled part.  A program
without the counter gives no reading.
"""

from __future__ import annotations

import weakref
from typing import Any, Dict, Optional

# one reading per traced run, keyed by the run's trace
_READINGS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
COMPILE_TIMES = ("trace_s", "lower_s", "compile_s")


def _take() -> Optional[Dict[str, Any]]:
    try:
        from repro.runtime import compile_cache
    except ImportError:
        return None
    read = getattr(compile_cache, "snapshot", None)
    return read() if read is not None else None


def reading(ctx) -> Optional[Dict[str, Any]]:
    """The counter's sums as the window left them, or None."""
    if ctx.trace is None:
        return None
    if ctx.trace not in _READINGS:
        _READINGS[ctx.trace] = {"snapshot": _take()}
    return _READINGS[ctx.trace]["snapshot"]


def setup_part(snap: Dict[str, Any]) -> Dict[str, float]:
    """The counter's sums over the events that fired outside the
    profiled window, by field."""
    return {k: snap[k] - snap["profiled"][k] for k in snap
            if k != "profiled"}


def setup_compile_s(snap: Dict[str, Any]) -> float:
    """Set-up time tracing, lowering and getting executables (the cache
    load lies inside ``compile_s``)."""
    part = setup_part(snap)
    return sum(part[k] for k in COMPILE_TIMES)
