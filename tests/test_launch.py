"""Launch plumbing: execution mode, compile-cache placement, meshes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.dist.sharding import logical, make_rules, use_rules
from repro.kernels.backend import resolve_interpret
from repro.launch import cache
from repro.launch.mesh import make_mesh, make_test_mesh


def test_interpret_mode_follows_the_backend():
    """Kernels interpret exactly on the CPU backend; an explicit argument
    wins, and no environment variable can force either mode."""
    assert resolve_interpret(None) == (jax.default_backend() == "cpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)


def test_cache_dir_from_environment_wins(monkeypatch, restore_cache_dir,
                                         tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    assert cache.use_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_cache_dir_defaults_to_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    path = cache.use_compilation_cache()
    assert path == str(cache.CHECKOUT_CACHE)
    assert cache.CHECKOUT_CACHE.name == ".jax_cache"
    assert (cache.CHECKOUT_CACHE.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    assert cache.use_compilation_cache() == path     # fixed, not per call
    # every program is written, not only those over JAX's 1 s default
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_meshes_have_auto_axes():
    """``logical()`` requests layouts; under Explicit axes (the default of
    recent ``jax.make_mesh``) the constraint would assert instead."""
    mesh = make_test_mesh(jax.device_count())
    assert set(mesh.axis_types) == {AxisType.Auto}
    assert make_mesh((1,), ("data",)).axis_types == (AxisType.Auto,)
    rules = make_rules(mesh.axis_names, fsdp_params=False)
    x = jnp.arange(8.0).reshape(2, 4)
    with jax.set_mesh(mesh), use_rules(rules):
        y = jax.jit(lambda v: logical(v * 2, "batch", "model"))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x) * 2)
