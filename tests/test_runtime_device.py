"""Device identity, the compile-cache location, and interpret-mode choice."""
import jax
import pytest

from repro.kernels import common as KC
from repro.runtime.device import CHECKOUT, configure_compile_cache, device_info


def test_device_info_names_what_jax_sees():
    info = device_info()
    assert info["platform"] == jax.devices()[0].platform
    assert info["kind"] == jax.devices()[0].device_kind
    assert info["count"] == len(jax.devices())


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = configure_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert configure_compile_cache() == path  # fixed: the same on every call


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env itself


@pytest.mark.parametrize("platform,interpret", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, interpret):
    monkeypatch.setattr(KC.jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            KC.interpret_default()
    else:
        assert KC.interpret_default() is interpret
