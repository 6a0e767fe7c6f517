"""Launcher environment rules: the persistent compilation cache's
directory (``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``), and the forced host device count for
``--mesh``, which only an explicit ``JAX_PLATFORMS=cpu`` allows."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cc

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax_cc.reset_cache()  # later compiles in this process see `before`


def test_cache_dir_rule():
    assert compile_cache.DEFAULT_DIR == CHECKOUT / ".jax_cache"
    assert compile_cache.cache_dir({}) == str(CHECKOUT / ".jax_cache")
    env = {"JAX_COMPILATION_CACHE_DIR": "/var/cache/jax"}
    assert compile_cache.cache_dir(env) == "/var/cache/jax"


def test_enable_without_env_uses_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert Path(path).is_dir()


def test_enable_with_env_sets_no_other_dir(monkeypatch, tmp_path,
                                           restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


@pytest.mark.parametrize("platforms,forced", [
    ("cpu", True), (None, False), ("tpu", False), ("", False)])
def test_mesh_device_fallback_only_on_explicit_cpu(monkeypatch, platforms,
                                                  forced):
    from repro.launch import serve

    monkeypatch.setattr(serve.sys, "argv", ["serve", "--mesh", "2x2"])
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    serve._mesh_device_fallback()
    flags = serve.os.environ.get("XLA_FLAGS", "")
    assert ("--xla_force_host_platform_device_count=4" in flags) == forced
