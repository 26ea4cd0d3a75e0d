"""The harness's contract on the CPU: the result line, refusing to run
without a TPU, and ``correct`` coming out false for each fault a cell can
have and for the control."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import small
from harness import spec

RUN = os.path.join(small.CHIP, "run.py")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_result_line_keys_untraced():
    r = small.run_small("kernelgen-e5.jacobi")
    assert list(r) == KEYS + ["checks"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"stencil_gpts_s", "setup_s"}
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    for c in r["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(r)


def test_result_line_keys_traced(monkeypatch):
    """A traced run reports the per-layer metrics, busy and window
    seconds, and the breakdown (the CPU has no device plane, so the
    recorded chip trace stands in for the profiler's)."""
    import contextlib
    from harness import trace as trace_mod
    import test_trace

    class Canned:
        def result(self, kernel_names=None):
            t = test_trace.recorded()
            t.kernel_names = tuple(kernel_names)
            return t

    monkeypatch.setattr(trace_mod, "capture",
                        contextlib.contextmanager(lambda on: (yield Canned())))
    r = small.run_small("kernelgen-e5.jacobi", trace=True)
    assert list(r) == KEYS + ["breakdown", "checks"]
    assert set(r["metrics"]) == {"fetch_bytes_ratio", "stencil_wrapper_share",
                                 "stencil_roofline", "device_idle.stencil"}
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(r["breakdown"]["device_ops"]) <= 10


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, spec.load_json(
            spec.CHECKOUT / "BENCHMARK.json")["paths"][0], "run.py"),
         "--workload", "kernelgen-e5.jacobi", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run_py(str(spec.CHECKOUT), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no program to run."""
    bench = spec.load_json(spec.CHECKOUT / "BENCHMARK.json")
    shutil.copy(spec.CHECKOUT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(spec.CHECKOUT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ---------------------------------------------------------------------------
# sound runs, faults and the control
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", ["kernelgen-e5.tricubic", "olmo-1b.train",
                                  "olmo-1b.decode", "kernelgen-e5.jacobi"])
def test_sound_run_is_correct(cell):
    r = small.run_small(cell, seconds=0.3)
    assert r["correct"] is True, r["checks"]


def _altered_stencil(monkeypatch):
    import repro.kernels.stencil as st
    real = st.stencil_apply

    def altered(*a, **k):
        out = real(*a, **k)
        return out.at[(0,) * out.ndim].add(1.0)
    monkeypatch.setattr(st, "stencil_apply", altered)


def _unchanged_state(monkeypatch):
    import repro.train as tr
    real = tr.make_train_step

    def make(*a, **k):
        step = real(*a, **k)

        def unchanged(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return unchanged
    monkeypatch.setattr(tr, "make_train_step", make)


def _half_batch(monkeypatch):
    import repro.train as tr
    real = tr.make_train_step

    def make(*a, **k):
        step = real(*a, **k)

        def half(params, opt_state, batch):
            labels = batch["labels"]
            keep = labels.shape[1] // 2
            batch = dict(batch, labels=labels.at[:, keep:].set(-1))
            return step(params, opt_state, batch)
        return half
    monkeypatch.setattr(tr, "make_train_step", make)


def _altered_token(monkeypatch):
    import jax.numpy as jnp
    import repro.serve.step as sv
    real = sv.make_decode_step

    def make(model, *a, **k):
        step = real(model, *a, **k)

        def altered(params, tokens, cache, rng):
            # every fifth position's token, one id off
            nxt, new_cache = step(params, tokens, cache, rng)
            wrong = (nxt + 1) % model.cfg.vocab
            return jnp.where(cache["pos"] % 5 == 3, wrong, nxt), new_cache
        return altered
    monkeypatch.setattr(sv, "make_decode_step", make)


@pytest.mark.parametrize("cell,fault", [
    ("kernelgen-e5.tricubic", _altered_stencil),
    ("kernelgen-e5.jacobi", _altered_stencil),
    ("olmo-1b.train", _unchanged_state),
    ("olmo-1b.train", _half_batch),
    ("olmo-1b.decode", _altered_token),
])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    r = small.run_small(cell, seconds=0.3)
    assert r["correct"] is False, r["checks"]
    assert r["failed"] > 0


@pytest.mark.parametrize("cell,config,traffic", [
    ("kernelgen-e5.tricubic", None, None),
    ("olmo-1b.train", None, None),
    # the float8 gap grows with the logits' spread and with the positions
    # served; at the smallest sizes it reads about 0.05, under the limit
    ("olmo-1b.decode", {"hidden_size": 512, "intermediate_size": 1024},
     {"gen": 96}),
    ("kernelgen-e5.jacobi", None, None),
])
def test_control_is_not_correct(cell, config, traffic):
    """The reference in the precision below the configuration's, in the
    program's place, fails at least one limit."""
    import jax
    import control
    cell = small.small_cell(cell, config=config, traffic=traffic)
    got = control.readings(cell, 2**31 + 5, 0.3, jax.devices())["control"]
    limits = cell.traffic["limits"]
    assert any(got[k] > limits[k] for k in got if k in limits), got


def test_decode_check_spreads_its_rows_over_the_batch():
    """The decode check draws one request from each of ``check_requests``
    equal stretches of the batch, the same ones for the same seed."""
    import types
    cell = spec.find_cell("olmo-1b.decode")
    rows_of = cell.driver().Driver._check_rows
    n = cell.traffic["check_requests"]
    for seed in (2**31 + 3, 2**33 + 7):
        fake = types.SimpleNamespace(B=16, ctx=types.SimpleNamespace(
            seed=seed, traffic={"check_requests": n}))
        rows = rows_of(fake)
        assert rows == rows_of(fake)
        assert [r * n // 16 for r in rows] == list(range(n))


def test_reference_gradient_difference_from_itself_is_nought():
    """The train reference's ``grad_diff_norms`` against its own first
    gradient read 0, and against another seed's do not."""
    import jax
    from harness.context import seed_key
    cell = small.small_cell("olmo-1b.train")
    ref, cfg = cell.reference(), cell.config
    opt = cell.traffic["optimizer"]
    rng = jax.random.PRNGKey(1)
    toks = jax.random.randint(rng, (1, 9), 0, cfg["vocab_size"])
    batches = [(toks[:, :-1], toks[:, 1:])]
    one = ref.train_steps(seed_key(5), cfg, opt, batches, keep_grad=True)
    same = ref.train_steps(seed_key(5), cfg, opt, batches,
                           grad_against=one["first_grad"])
    other = ref.train_steps(seed_key(6), cfg, opt, batches,
                            grad_against=one["first_grad"])
    assert max(same["grad_diff_norms"].values()) == 0.0
    assert min(other["grad_diff_norms"].values()) > 0.0



def test_train_losses_read_late_are_the_same_losses():
    """The train driver keeps ``ahead_steps`` steps queued, reads their
    losses in order, and ``finish`` drains the queue: the losses equal
    those read after every step."""
    import jax
    import run
    from harness.context import RunContext

    def losses(ahead_steps):
        cell = small.small_cell("olmo-1b.train",
                                traffic={"ahead_steps": ahead_steps})
        drv = cell.driver().Driver(RunContext(cell=cell, seed=2**31 + 5,
                                              devices=jax.devices()))
        assert not drv.pending
        drv.begin()
        for _ in range(5):
            drv.unit()
        assert len(drv.pending) == ahead_steps
        run.finish(drv)
        assert not drv.pending and drv.steps_in_window == 5
        return drv.losses

    late = losses(3)
    assert len(late) == 3 + 5
    assert late == losses(0)
