"""``enable_compile_cache``: where JAX's persistent cache lands."""
import os
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from conftest import REPO, SRC
from repro.launch import jax_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_jax_config():
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_unset_env_puts_the_cache_in_the_checkout(monkeypatch,
                                                  restore_jax_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = jax_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


SET_ENV = r"""
import jax, jax.numpy as jnp
from repro.launch.jax_cache import enable_compile_cache
print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def test_set_env_is_the_only_cache_dir(tmp_path):
    cache = str(tmp_path / "cache")
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu", "PYTHONPATH": SRC,
           "JAX_COMPILATION_CACHE_DIR": cache}
    proc = subprocess.run([sys.executable, "-c", SET_ENV], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [cache, cache]
    assert os.listdir(cache)                       # the program landed
