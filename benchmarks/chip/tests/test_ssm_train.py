"""The ``mamba2-1.3b.train`` cell rehearsed on the CPU at a small size,
its counts, and the join of a device trace to the program's named scopes
(``harness/scopes.py``)."""

import types

import pytest

import small
from harness import scopes, spec, ssm_counts
from test_run import _half_batch, _unchanged_state
from test_trace import made_up

CELL = "mamba2-1.3b.train"
SMALL_CONFIG = {"d_model": 64, "n_layer": 2, "d_state": 16, "headdim": 16,
                "chunk_size": 16, "vocab_size": 256}
SMALL_TRAFFIC = {"seq_len": 64}


def _cell():
    return small.small_cell(CELL, config=SMALL_CONFIG, traffic=SMALL_TRAFFIC)


def _run(**kw):
    return small.run_cell_small(_cell(), seconds=0.3, **kw)


def test_three_units_report_train_tokens():
    import jax
    from harness.context import RunContext
    cell = _cell()
    drv = cell.driver().Driver(RunContext(cell=cell, seed=2**31 + 13,
                                          devices=jax.devices()))
    drv.begin()
    for _ in range(3):
        drv.unit()
    drv.finish()
    facts = drv.facts(1.0)
    assert facts["units"] == 3
    assert facts["end_to_end"]["train_tokens_s"] == 3 * 64
    # the compiled step's HLO carries the scopes the readers look for
    names = scopes.op_names(facts["hlo_text"]())
    for scope in ("mamba2_ssd", "mamba2_conv1d"):
        assert any(scope in n for n in names.values()), scope


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"train_tokens_s", "setup_s"}


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = _run()
    assert r["correct"] is False, r["checks"]


def test_control_is_not_correct():
    import jax
    import control
    cell = _cell()
    got = control.readings(cell, 2**31 + 5, 0.3, jax.devices())["control"]
    limits = cell.traffic["limits"]
    assert any(got[k] > limits[k] for k in got if k in limits), got


def test_counts_at_published_depth():
    """The published model: 25.85 M parameters a layer, 1.344 B in all;
    one layer's SSD forward over 2,048 tokens about 6.5 GFLOP and 35 MB."""
    cfg = dict(spec.find_cell(CELL).config, n_layer=48)
    s = ssm_counts.sizes(cfg)
    assert s["per_layer"] == 25_849_280
    assert s["params"] == 1_343_757_312
    assert ssm_counts.ssd_forward_flops(cfg, 2048) == pytest.approx(6.5e9, rel=0.01)
    assert ssm_counts.ssd_forward_min_bytes(cfg, 2048) == pytest.approx(35e6, rel=0.01)


HLO = """\
HloModule jit_train_step

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %exp.1 = f32[8]{0} exponential(%param_0), metadata={op_name="jit(train_step)/jvp()/mamba2_ssd/exp"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/jvp()/mamba2_ssd/exp"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fc2, metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/mamba2_conv1d/mul"}
  %copy.3 = f32[8]{0} copy(%fusion.2)
  ROOT %fusion.4 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fc4, metadata={op_name="jit(train_step)/transpose(jvp())/mamba2_ssd/add" source_file="x.py" source_line=3}
}
"""


def test_op_names_by_instruction():
    names = scopes.op_names(HLO)
    assert names["fusion.1"].endswith("mamba2_ssd/exp")
    assert names["fusion.4"].endswith("mamba2_ssd/add")
    assert names["copy.3"] == "" and names["p"] == ""
    assert scopes.instruction("%fusion.2 = f32[8]{0} fusion(%fusion.1)") == "fusion.2"


def _ops(*named):
    """Device events named as the trace names them, one after another."""
    out, t = [], 0
    for name, dur in named:
        out.append((f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", t, t + dur))
        t += dur
    return out


def test_scope_seconds_forward_and_backward():
    names = scopes.op_names(HLO)
    loop = ("%while.9 = (f32[8]{0}) while((f32[8]{0}) %t)", 0, 65)
    t = made_up(_ops(("fusion.1", 30), ("fusion.2", 10), ("copy.3", 5),
                     ("fusion.4", 20)) + [loop], window=(0, 100))
    assert scopes.scope_s(t, names, "mamba2_ssd") == pytest.approx(50e-9)
    assert scopes.scope_s(t, names, "mamba2_conv1d") == pytest.approx(10e-9)


def test_join_under_coverage_reads_nothing():
    """An operation the HLO does not name is not joined; at 95 % of the
    operation time joined a scope is read, below it nothing is."""
    names = scopes.op_names(HLO)
    read = lambda unknown: scopes.scope_s(made_up(_ops(
        ("fusion.1", 95 - unknown), ("copy.3", 5), ("fusion.77", unknown)),
        window=(0, 100)), names, "mamba2_ssd")
    assert read(5) == pytest.approx(90e-9)
    assert read(6) is None


def test_readers_join_once_and_need_a_traced_run():
    calls = []

    def hlo_text():
        calls.append(1)
        return HLO

    t = made_up(_ops(("fusion.1", 40), ("fusion.2", 10)), window=(0, 100))
    ctx = types.SimpleNamespace(trace=t, peaks=lambda: {
        "bf16_flops_s": 1e9, "hbm_bytes_s": 1e9})
    facts = {"hlo_text": hlo_text, "units": 2,
             "ssd_train_step": {"flops": 4.0, "min_bytes": 10.0}}
    read = lambda m: spec.load_module(spec.HERE / "metrics" / f"{m}.py").read(
        ctx, facts, t)
    assert read("ssd_share") == pytest.approx(80.0)
    assert read("conv1d_share") == pytest.approx(20.0)
    # 2 steps of at least 10 ns (bytes-bound) against 40 ns under the scope
    assert read("ssd_roofline") == pytest.approx(50.0)
    assert len(calls) == 1
    untraced = types.SimpleNamespace(trace=None)
    assert scopes.read_scope_s(untraced, facts, "mamba2_ssd") is None
