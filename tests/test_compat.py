"""Mesh construction and the compile-cache placement helper."""

from pathlib import Path

import jax
import pytest
from jax.sharding import AxisType

from repro.core import compat


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compat.use_compile_cache()
    repo = Path(__file__).resolve().parents[1]
    assert got == str(repo / ".jax_cache") == str(compat.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compat.use_compile_cache() == got  # the same path on every call


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compat.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to jax itself


def test_make_mesh_axes_are_auto():
    mesh = compat.make_mesh_1d(1)
    assert dict(mesh.shape) == {"model": 1}
    assert mesh.axis_types == (AxisType.Auto,)
