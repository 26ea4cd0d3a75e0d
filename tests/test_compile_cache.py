"""The persistent compilation cache lands where the environment says,
or at one fixed path inside the checkout."""

import pathlib

import jax
import pytest

from repro.runtime import compile_cache
from repro.runtime.compile_cache import enable_compile_cache

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_honoured(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets nothing
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_and_inside_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = enable_compile_cache()
    second = enable_compile_cache()
    assert first == second == jax.config.jax_compilation_cache_dir
    path = pathlib.Path(first)
    assert path == CHECKOUT / ".jax_cache"
    assert CHECKOUT in path.parents


@pytest.fixture
def fresh_cache(tmp_path, cache_config):
    """Every compile written to an empty persistent cache in tmp_path."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _delta(after, before):
    return {k: after[k] - before[k] for k in compile_cache.FIELDS}


def test_counter_counts_a_compile_then_a_cache_hit(fresh_cache):
    import numpy as np
    counter = compile_cache.compile_counter()
    assert compile_cache.compile_counter() is counter      # registered once
    f = jax.jit(lambda x: jax.numpy.sin(x) * 3.0 + 1.0)
    x = np.arange(5.0, dtype=np.float32)
    s0 = counter.snapshot()
    f(x).block_until_ready()
    s1 = counter.snapshot()
    jax.clear_caches()                    # the next call asks again
    f(x).block_until_ready()
    s2 = counter.snapshot()
    first, second = _delta(s1, s0), _delta(s2, s1)
    assert first["compile_requests"] == 1 and first["cache_misses"] == 1
    assert first["cache_hits"] == 0 and first["cache_load_s"] == 0
    assert first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["compile_s"] > 0
    assert second["compile_requests"] == 1 and second["cache_hits"] == 1
    assert second["cache_misses"] == 0
    assert 0 < second["cache_load_s"] <= second["compile_s"]
    # nothing here ran under a profile
    assert _delta(s2["profiled"], s0["profiled"]) == dict.fromkeys(
        compile_cache.FIELDS, 0)
    assert compile_cache.snapshot() is not None


def test_counter_keeps_apart_what_compiles_under_a_profile(tmp_path):
    import numpy as np
    counter = compile_cache.compile_counter()
    f = jax.jit(lambda x: jax.numpy.cos(x) - 2.0)
    s0 = counter.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(np.ones(3, np.float32)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    s1 = counter.snapshot()
    profiled = _delta(s1["profiled"], s0["profiled"])
    assert profiled["compile_requests"] == 1
    assert _delta(s1, s0)["compile_requests"] == 1
