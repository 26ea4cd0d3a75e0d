"""Substrate tests: optimizer, data pipeline, checkpointing, runtime
health, sharding rules, HLO analyzer."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:               # degrade: property tests skip
    from _hypothesis_stub import given, settings, st

from repro.checkpoint import CheckpointStore
from repro.data import DataConfig, TokenPipeline
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_host_mesh
from repro.runtime import Heartbeat, StragglerDetector, plan_elastic
from repro.sharding.rules import resolve_spec
from repro.train import OptConfig, adamw_update, decay_mask, init_opt_state
from repro.train.optim import global_norm, lr_schedule


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_decreases_quadratic():
    cfg = OptConfig(lr=0.1, warmup_steps=1, total_steps=100,
                    weight_decay=0.0, clip_norm=100.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_opt_state(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}       # d/dw ||w||^2
        params, state, _ = adamw_update(cfg, grads, state, params,
                                        decay_mask(params))
    assert float(jnp.sum(jnp.abs(params["w"]))) < 0.5


def test_grad_clipping():
    cfg = OptConfig(lr=1e-3, clip_norm=1.0, warmup_steps=1, total_steps=10)
    params = {"w": jnp.zeros(4)}
    state = init_opt_state(params)
    big = {"w": jnp.full(4, 1e6)}
    _, _, metrics = adamw_update(cfg, big, state, params, decay_mask(params))
    assert float(metrics["grad_norm"]) > 1e5   # reported pre-clip


def test_lr_schedule_shape():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                    min_lr_frac=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in
           (0, 5, 10, 55, 100)]
    assert lrs[1] == pytest.approx(0.5)     # mid-warmup
    assert lrs[2] == pytest.approx(1.0)     # peak
    assert lrs[2] > lrs[3] > lrs[4]
    assert lrs[4] == pytest.approx(0.1, abs=1e-6)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_determinism():
    cfg = DataConfig(vocab=100, seq_len=16, global_batch=4, seed=7)
    a = TokenPipeline(cfg).batch_at(3)
    b = TokenPipeline(cfg).batch_at(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = TokenPipeline(cfg).batch_at(4)
    assert not np.array_equal(a["tokens"], c["tokens"])


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 50), st.sampled_from([1, 2, 4]))
def test_data_host_slicing(step, hosts):
    cfg = DataConfig(vocab=64, seq_len=8, global_batch=8, seed=1)
    pipe = TokenPipeline(cfg)
    full = pipe.batch_at(step)
    per = cfg.global_batch // hosts
    for h in range(hosts):
        part = pipe.batch_at(step, host_slice=(h, hosts))
        np.testing.assert_array_equal(
            part["tokens"], full["tokens"][h * per:(h + 1) * per])


def test_labels_shifted():
    cfg = DataConfig(vocab=64, seq_len=8, global_batch=2)
    b = TokenPipeline(cfg).batch_at(0)
    assert b["tokens"].shape == b["labels"].shape == (2, 8)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path))
    state = {"p": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "n": jnp.asarray(3)}
    store.save(10, state, extra={"data_step": 10})
    assert store.latest_step() == 10
    got, extra = store.restore(10, state)
    np.testing.assert_array_equal(np.asarray(got["p"]), np.asarray(state["p"]))
    assert extra["data_step"] == 10


def test_checkpoint_atomicity(tmp_path):
    """A half-written (no manifest) checkpoint is never 'latest'."""
    store = CheckpointStore(str(tmp_path))
    state = {"p": jnp.ones(4)}
    store.save(1, state)
    # simulate a crash mid-write of step 2
    broken = tmp_path / "step_2"
    broken.mkdir()
    (broken / "leaf_00000.npy").write_bytes(b"garbage")
    assert store.latest_step() == 1


def test_checkpoint_corruption_detected(tmp_path):
    store = CheckpointStore(str(tmp_path))
    state = {"p": jnp.ones(4)}
    store.save(1, state)
    # flip bytes in the stored leaf
    leaf = tmp_path / "step_1" / "leaf_00000.npy"
    data = bytearray(leaf.read_bytes())
    data[-1] ^= 0xFF
    leaf.write_bytes(bytes(data))
    with pytest.raises(IOError):
        store.restore(1, state)


def test_checkpoint_async_and_gc(tmp_path):
    store = CheckpointStore(str(tmp_path))
    state = {"p": jnp.ones(4)}
    for s in (1, 2, 3, 4):
        store.save_async(s, state)
    store.wait()
    assert store.latest_step() == 4
    store.gc(keep=2)
    assert store.latest_step() == 4
    assert not (tmp_path / "step_1").exists()


def test_checkpoint_reshard_on_load(tmp_path):
    """Mesh-shape independence: restore with explicit shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_host_mesh()
    store = CheckpointStore(str(tmp_path))
    state = {"p": jnp.arange(8, dtype=jnp.float32)}
    store.save(5, state)
    sh = {"p": NamedSharding(mesh, P())}
    got, _ = store.restore(5, state, shardings=sh)
    assert got["p"].sharding == sh["p"]


# ---------------------------------------------------------------------------
# runtime health
# ---------------------------------------------------------------------------

def test_heartbeat_death_detection():
    hb = Heartbeat(["a", "b"], lease_s=10.0)
    hb.beat("a", 5, now=100.0)
    hb.beat("b", 5, now=100.0)
    assert hb.dead_hosts(now=105.0) == []
    hb.beat("a", 6, now=115.0)
    assert hb.dead_hosts(now=115.0) == ["b"]
    assert hb.watermark() == 5


def test_straggler_detection():
    det = StragglerDetector(threshold=1.5, patience=2)
    t_ok = {"a": 1.0, "b": 1.0, "c": 1.0}
    t_slow = {"a": 1.0, "b": 1.0, "c": 2.5}
    assert det.observe_step(t_ok) == []
    assert det.observe_step(t_slow) == []        # patience 1/2
    assert det.observe_step(t_slow) == ["c"]     # flagged
    assert det.observe_step(t_ok) == []          # streak reset


def test_elastic_plan():
    plan = plan_elastic([f"h{i}" for i in range(128)], chips_per_host=4,
                        model_axis=16)
    assert plan.mesh_shape == (32, 16)           # 512 chips
    plan2 = plan_elastic([f"h{i}" for i in range(100)], chips_per_host=4)
    assert plan2.mesh_shape == (16, 16)          # shrink to 256 chips
    assert len(plan2.host_slices) == 64


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

class _FakeMesh:
    """resolve_spec only reads mesh.shape; avoids needing real devices."""

    def __init__(self, **axes):
        self.shape = dict(axes)


def test_resolve_spec_divisibility_fallback():
    from jax.sharding import PartitionSpec as P
    mesh = make_host_mesh()   # (1,1): everything divides
    spec = resolve_spec((64, 32), ("vocab", "embed"), mesh)
    assert spec == P("model", "data")
    # 4 kv heads cannot shard over a 16-wide model axis
    mesh16 = _FakeMesh(data=1, model=16)
    spec = resolve_spec((64, 4, 8), ("embed", "kv_heads", "head_dim"), mesh16)
    assert len(spec) < 2 or spec[1] is None      # kv replicated
    rep = []
    resolve_spec((64, 4, 8), ("embed", "kv_heads", "head_dim"), mesh16,
                 report=rep)
    assert any("kv_heads" in r for r in rep)


def test_resolve_spec_no_duplicate_axis():
    from jax.sharding import PartitionSpec as P
    mesh = _FakeMesh(data=2, model=2)
    # two dims both mapped to 'model': second must fall back
    spec = resolve_spec((8, 8), ("vocab", "ff"), mesh)
    assert spec == P("model")


# ---------------------------------------------------------------------------
# HLO analyzer (trip-count correction)
# ---------------------------------------------------------------------------

_HLO = """
HloModule test

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16] get-tuple-element(%p), index=1
  %w = f32[16,16] constant({...})
  %dot.1 = f32[8,16] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%ip, %dot.1)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%z, %a)
  %w2 = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,16] get-tuple-element(%w2), index=1
}
"""


def test_hlo_analyzer_trip_scaling():
    stats = analyze(_HLO)
    # one dot of 2*8*16*16 flops, executed 12 times
    assert stats.flops == pytest.approx(12 * 2 * 8 * 16 * 16)
    assert stats.n_while == 1 and stats.trip_counts == [12]


def test_hlo_analyzer_collectives():
    hlo = _HLO.replace(
        "ROOT %out = f32[8,16] get-tuple-element(%w2), index=1",
        "%g = f32[8,16] get-tuple-element(%w2), index=1\n"
        "  ROOT %ar = f32[8,16] all-reduce(%g), to_apply=%cond")
    stats = analyze(hlo)
    assert stats.collective_bytes["all-reduce"] == pytest.approx(8 * 16 * 4)
