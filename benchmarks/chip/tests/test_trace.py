"""The reduction from a device trace to busy time, kernel time and the
time outside the kernel, on a small recorded trace and on made-up ones."""

import os

import pytest

from harness import spec
from harness.trace import (SPAN_PREFIX, WINDOW, Trace, dispatch_events,
                           op_label, read_xplane)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "jacobi_1024.xplane.pb")
KERNEL = 'custom_call_target="tpu_custom_call"'


def recorded(dispatch: bool = True) -> Trace:
    """Three sweeps of the jitted jacobi ``stencil_apply`` (plan
    ``paper``) at 1024x1024 on one TPU v5e chip, each under a host span
    ``sweep``; the window runs from the first sweep's start to the last
    one's end.  JAX's dispatch events are kept where ``dispatch``."""
    devices, host = read_xplane(RECORDED)
    sweeps = [(SPAN_PREFIX + n, s, e) for n, s, e in host if n == "sweep"]
    window = (WINDOW, min(s for _, s, _ in sweeps), max(e for _, _, e in sweeps))
    return Trace(devices, [window] + sweeps, kernel_names=[KERNEL],
                 dispatch=dispatch_events(host) if dispatch else ())


def made_up(ops, spans=(), window=(0, 100), dispatch=()):
    spans = [(WINDOW,) + tuple(window)] + [
        (SPAN_PREFIX + n, s, e) for n, s, e in spans]
    return Trace({"/device:TPU:0": list(ops)}, spans, kernel_names=["kern"],
                 dispatch=dispatch)


def test_busy_is_the_union_of_operations():
    t = made_up([("a", 10, 30), ("b", 20, 40), ("c", 60, 70)])
    assert t.busy_s() == pytest.approx(40e-9)
    assert t.window_s() == pytest.approx(100e-9)


def test_operations_are_clipped_to_the_window():
    t = made_up([("a", -50, 10), ("b", 90, 150)], window=(0, 100))
    assert t.busy_s() == pytest.approx(20e-9)


def test_loops_count_once():
    """A loop's event spans its body's events: busy counts it once, and
    sums leave the loop itself out."""
    t = made_up([("%while.1 = (s32[])", 0, 50), ("%fusion.2 = f32[]", 5, 20),
                 ("%fusion.3 = f32[] kern", 20, 45)])
    assert t.busy_s() == pytest.approx(50e-9)
    assert t.op_s() == pytest.approx(40e-9)
    assert t.kernel_s() == pytest.approx(25e-9)


@pytest.mark.parametrize("dispatch,label", [
    ((), "decode_step"),
    ([("PjitFunction(decode)", 70, 80),
      ("PJRT_LoadedExecutable_Execute", 72, 78)], "decode_step/dispatch"),
])
def test_idle_gaps_go_to_the_host_span_covering_them(dispatch, label):
    t = made_up([("a", 0, 10), ("b", 30, 60), ("c", 90, 100)],
                spans=[("prefill", 5, 35), ("decode_step", 55, 95),
                       ("sample", 70, 75)], dispatch=dispatch)
    gaps = dict(t.breakdown()["idle_gaps"])
    assert gaps == {"prefill": pytest.approx(20e-9),
                    label: pytest.approx(30e-9)}


def test_op_label_keeps_name_type_and_target():
    hlo = ('%_unknown_.1 = f32[1024,1024]{1,0:T(8,128)} custom-call(f32[8] '
           '%x), custom_call_target="tpu_custom_call", operand_layout=...')
    assert op_label(hlo) == ("%_unknown_.1 f32[1024,1024]{1,0:T(8,128)} "
                             "tpu_custom_call")


def test_recorded_trace():
    t = recorded()
    assert list(t.devices) == ["/device:TPU:0"]
    window, busy = t.window_s(), t.busy_s()
    assert 0 < busy < window
    kernel, total = t.kernel_s(), t.op_s()
    assert 0 < kernel < total <= busy * (1 + 1e-9)
    # the kernel is the Pallas custom call, and is most of the device time
    assert kernel / total > 0.5
    b = t.breakdown()
    assert b["device_ops"][0][0].endswith("tpu_custom_call")
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(window - busy)
    assert {n for n, _ in b["idle_gaps"]} <= {
        "sweep", "sweep/dispatch", "outside any span"}


def test_dispatch_labels_change_only_the_labels():
    """On the recorded trace, gaps under JAX's dispatch events take the
    label ``sweep/dispatch``; busy and window seconds, every device idle
    share and the idle seconds under each span are as without them."""
    plain, labelled = recorded(dispatch=False), recorded()
    assert labelled.busy_s() == plain.busy_s()
    assert labelled.window_s() == plain.window_s()
    for name in ("device_idle.stencil", "device_idle.train",
                 "device_idle.decode"):
        read = spec.load_module(spec.HERE / "metrics" / f"{name}.py").read
        assert read(None, {}, labelled) == read(None, {}, plain)
    before = dict(plain.breakdown()["idle_gaps"])
    after = dict(labelled.breakdown()["idle_gaps"])
    assert "sweep/dispatch" in after and "sweep/dispatch" not in before
    by_span = {}
    for label, s in after.items():
        span = label.removesuffix("/dispatch")
        by_span[span] = by_span.get(span, 0.0) + s
    assert by_span == pytest.approx(before)
    assert labelled.breakdown()["device_ops"] == plain.breakdown()["device_ops"]
