"""``repro.launch.env``: where JAX's persistent compilation cache lives,
and where a function that needs several devices runs."""
import os

import jax
import pytest

from repro.launch.env import call_on_devices, use_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_and_ignored(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_compile_cache")
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def device_count():
    return len(jax.devices())


def test_call_on_devices_in_process():
    assert call_on_devices(1, device_count) == len(jax.devices())


def test_call_on_devices_cpu_child():
    """A CPU process with too few devices runs the function in a child
    with that many virtual host devices and gets its result back."""
    n = len(jax.devices()) + 1
    assert call_on_devices(n, device_count) == n
