"""Persistent XLA compilation cache, placed from outside the program,
and a counter of what compiling costs.

The cache directory is part of what makes an entry findable again, so it
must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` wins when the
environment sets it (JAX reads it itself), and otherwise the cache lives
at the fixed path ``<checkout>/.jax_cache``.

``enable_compile_cache`` also registers, once per process, a listener on
JAX's compile events (``jax.monitoring``): it sums the time spent
tracing, lowering, and getting executables (``compile_s``: a backend
compile, or on a persistent-cache hit the lookup and load, whose part
``cache_load_s`` is also summed alone), and counts the cache's hits and
misses and the compile requests (executables asked of the backend or
the cache).  ``snapshot()`` reads the sums.  Events that fire while a
profiler trace is being recorded are also summed apart, under
``"profiled"``, so that a traced stretch of a run can show it compiled
nothing.
"""

from __future__ import annotations

import os
import pathlib
import threading
from typing import Dict, Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, start
    the compile counter, and return that directory.  Call before the
    first compile to cache and count."""
    compile_counter()
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


# JAX's duration events, by the name each one's sum takes
DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# JAX times every executable it asks for, compiled or loaded from the cache
REQUEST_EVENT = "/jax/core/compile/backend_compile_duration"
FIELDS = tuple(DURATIONS.values()) + tuple(COUNTS.values()) \
    + ("compile_requests",)


class CompileCounter:
    """Sums of JAX's compile events since the counter was registered."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sums = {False: dict.fromkeys(FIELDS, 0),
                      True: dict.fromkeys(FIELDS, 0)}

    def _add(self, event: str, field: str, amount) -> None:
        profiled = jax.profiler.TraceAnnotation.is_enabled()
        with self._lock:
            self._sums[profiled][field] += amount
            if event == REQUEST_EVENT:
                self._sums[profiled]["compile_requests"] += 1

    def on_duration(self, event: str, duration_secs: float, **kwargs) -> None:
        if event in DURATIONS:
            self._add(event, DURATIONS[event], duration_secs)

    def on_event(self, event: str, **kwargs) -> None:
        if event in COUNTS:
            self._add(event, COUNTS[event], 1)

    def snapshot(self) -> Dict[str, object]:
        """The sums over every event so far, and under ``"profiled"``
        those of the events that fired while a profiler trace ran.
        ``trace_s + lower_s + compile_s`` is the whole time compiling;
        ``cache_load_s`` lies inside ``compile_s``."""
        with self._lock:
            quiet, traced = dict(self._sums[False]), dict(self._sums[True])
        out: Dict[str, object] = {k: quiet[k] + traced[k] for k in FIELDS}
        out["profiled"] = traced
        return out


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    """The process's counter, registered with ``jax.monitoring`` on the
    first call."""
    global _COUNTER
    if _COUNTER is None:
        counter = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(
            counter.on_duration)
        jax.monitoring.register_event_listener(counter.on_event)
        _COUNTER = counter
    return _COUNTER


def snapshot() -> Optional[Dict[str, object]]:
    """The counter's sums, or None where no counter was registered."""
    return _COUNTER.snapshot() if _COUNTER is not None else None
