"""Profiler capture and the reduction from trace to numbers.

A traced run records the window under ``jax.profiler`` with host spans
(``span``) around each unit of work.  ``Trace`` keeps, per device, the
operations of the device plane's ``XLA Ops`` line, and the host spans,
on the profiler's one clock.  Every number is taken inside the host
span ``window``:

- busy time: the union of the device's operation intervals;
- operation time: the sum of the durations of the operations a
  predicate selects (a kernel's time, or all operations' time; a loop's
  event contains its body's, so loops are left out of sums);
- idle gaps: the stretches of the window in which no operation ran,
  each put down to the innermost host span that covers its middle, as
  ``<span>/dispatch`` where one of JAX's dispatch events covers it too.

Per-device numbers are averaged over the chips used.
"""

from __future__ import annotations

import contextlib
import glob
import os
import tempfile
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
WINDOW = SPAN_PREFIX + "window"
# operations that contain others on the same line (a loop and its body)
CONTAINERS = ("%while", "%conditional", "%call")
# host events of JAX's dispatch: a jitted call, and its launch
DISPATCH = ("PjitFunction(", "PJRT_LoadedExecutable_Execute")

Op = Tuple[str, int, int]            # (name, start_ns, end_ns)


def span(name: str):
    """A host span ``bench/<name>`` in the profiler's trace (nearly free
    when no trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def op_label(hlo: str) -> str:
    """A short, stable name for an operation of the device trace, whose
    event names are whole HLO instructions: the instruction's name and
    its result type, and a custom call's target."""
    name, _, rest = hlo.partition(" = ")
    label = name + " " + rest.split(" ", 1)[0] if rest else name
    if 'custom_call_target="' in hlo:
        label += " " + hlo.split('custom_call_target="', 1)[1].split('"')[0]
    return label[:160]


class Trace:
    def __init__(self, devices: Dict[str, List[Op]], spans: List[Op],
                 kernel_names: Optional[Sequence[str]] = None,
                 dispatch: Iterable[Op] = ()):
        self.devices = devices
        self.spans = spans
        self.dispatch = self._union(list(dispatch))
        self.kernel_names = tuple(kernel_names or ())
        wins = [s for s in spans if s[0] == WINDOW]
        if not wins:
            raise ValueError("the trace has no host span 'window'")
        if not devices:
            raise ValueError("the trace has no device plane with operations")
        _, self.lo, self.hi = wins[0]

    # -- construction ------------------------------------------------------
    @classmethod
    def from_file(cls, path: str,
                  kernel_names: Optional[Sequence[str]] = None) -> "Trace":
        devices, host = read_xplane(path)
        spans = [ev for ev in host if ev[0].startswith(SPAN_PREFIX)]
        return cls(devices, spans, kernel_names, dispatch_events(host))

    # -- helpers -----------------------------------------------------------
    def _clipped(self, ops: Iterable[Op]) -> List[Op]:
        out = []
        for name, s, e in ops:
            s, e = max(s, self.lo), min(e, self.hi)
            if e > s:
                out.append((name, s, e))
        return out

    def _mean(self, per_device: Callable[[List[Op]], float]) -> float:
        vals = [per_device(self._clipped(ops)) for ops in self.devices.values()]
        return sum(vals) / len(vals)

    @staticmethod
    def _union(ops: List[Op]) -> List[Tuple[int, int]]:
        merged: List[Tuple[int, int]] = []
        for _, s, e in sorted(ops, key=lambda o: o[1]):
            if merged and s <= merged[-1][1]:
                if e > merged[-1][1]:
                    merged[-1] = (merged[-1][0], e)
            else:
                merged.append((s, e))
        return merged

    def is_kernel(self, name: str) -> bool:
        return any(k in name for k in self.kernel_names)

    # -- numbers -----------------------------------------------------------
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        return self._mean(
            lambda ops: sum(e - s for s, e in self._union(ops)) / 1e9)

    def op_s(self, select: Optional[Callable[[str], bool]] = None) -> float:
        """Summed duration of the selected operations in the window."""
        return self._mean(lambda ops: sum(
            e - s for n, s, e in ops if not n.startswith(CONTAINERS)
            and (select is None or select(n))) / 1e9)

    def kernel_s(self) -> float:
        return self.op_s(self.is_kernel)

    def idle_gaps(self) -> List[Tuple[int, int]]:
        """Idle stretches of the first device inside the window."""
        ops = self._clipped(next(iter(self.devices.values())))
        gaps, t = [], self.lo
        for s, e in self._union(ops):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.hi > t:
            gaps.append((t, self.hi))
        return gaps

    def host_activity(self, times: Sequence[int]) -> List[str]:
        """For each of the ascending ``times``, the innermost host span
        covering it, other than the window, and ``/dispatch`` after it
        where a dispatch event covers it too."""
        spans = sorted((s for s in self.spans if s[0] != WINDOW),
                       key=lambda s: s[1])
        out, active, i, j = [], [], 0, 0
        for t in times:
            while i < len(spans) and spans[i][1] <= t:
                active.append(spans[i])
                i += 1
            active = [s for s in active if s[2] > t]
            best = min(active, key=lambda s: s[2] - s[1], default=None)
            label = best[0][len(SPAN_PREFIX):] if best else "outside any span"
            while j < len(self.dispatch) and self.dispatch[j][1] <= t:
                j += 1
            if j < len(self.dispatch) and self.dispatch[j][0] <= t:
                label += "/dispatch"
            out.append(label)
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time, and the idle time
        by what the host was doing, each in seconds, at most ``top``."""
        by_op: Dict[str, float] = {}
        n = len(self.devices)
        for ops in self.devices.values():
            for name, s, e in self._clipped(ops):
                if name.startswith(CONTAINERS):
                    continue
                label = op_label(name)
                by_op[label] = by_op.get(label, 0.0) + (e - s) / 1e9 / n
        by_host: Dict[str, float] = {}
        gaps = self.idle_gaps()
        for (s, e), label in zip(gaps, self.host_activity(
                [(s + e) // 2 for s, e in gaps])):
            by_host[label] = by_host.get(label, 0.0) + (e - s) / 1e9
        rank = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by_op), "idle_gaps": rank(by_host)}


def dispatch_events(host: Iterable[Op]) -> List[Op]:
    """The host events in which JAX dispatches a jitted call."""
    return [ev for ev in host if ev[0].startswith(DISPATCH)]


def read_xplane(path: str, device_prefix: str = DEVICE_PREFIX):
    """The operations of each device plane's ``XLA Ops`` line, sorted by
    start, and every event of the host planes, as ``(name, start_ns,
    end_ns)`` on the profiler's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            ops = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            host.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for line in plane.lines for e in line.events)
    return devices, host


class _Capture:
    def __init__(self):
        self._trace: Optional[Trace] = None

    def result(self, kernel_names: Optional[Sequence[str]] = None) -> Trace:
        if self._trace is None:
            raise RuntimeError("no trace was taken")
        self._trace.kernel_names = tuple(kernel_names or ())
        return self._trace


@contextlib.contextmanager
def capture(enabled: bool):
    """Trace what runs inside the block when ``enabled``; the trace is
    read into memory and its files removed when the block ends."""
    cap = _Capture()
    if not enabled:
        yield cap
        return
    import jax
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d)
        try:
            yield cap
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        cap._trace = Trace.from_file(found[0])
