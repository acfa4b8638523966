"""The persistent compilation cache helper of the entry points: where the
cache lives, and that importing an entry point never turns it on."""
import importlib

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield old
    jax.config.update("jax_compilation_cache_dir", old)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def test_env_cache_dir_is_kept(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper changes nothing
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_default_cache_dir_is_fixed_in_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert (CHECKOUT / "src" / "repro" / "launch" / "compile_cache.py"
            ).is_file()
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("module", ["repro.launch.solve_server",
                                    "repro.serve.worker"])
def test_entry_point_import_leaves_cache_off(module, restore_cache_dir):
    importlib.import_module(module)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir
