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
