"""Launch plumbing: the persistent compile cache and serving meshes."""
import pathlib

import jax
import pytest

from repro.launch.compile_cache import enable_compile_cache

_ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX; without it the cache
    goes to one fixed git-ignored directory of the checkout, the same on
    every call."""
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            first, second = enable_compile_cache(), enable_compile_cache()
            assert first == second == str(_ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
            ignored = (_ROOT / ".gitignore").read_text().split()
            assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serving_mesh_axes_are_auto():
    """Models constrain activations with ``with_sharding_constraint``,
    which only Auto mesh axes accept."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh_for
    mesh = make_mesh_for(1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto,) * 2
