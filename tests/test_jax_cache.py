"""The compile-cache helper: one directory, decided in one place."""

import os
import subprocess
import sys

import jax
import pytest

from apex_tpu.utils import jax_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Hand the test the config as it is and put it back after."""
    was = jax.config.jax_compilation_cache_dir
    yield was
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_set_leaves_config_alone(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax_cache.enable_compile_cache() is None
    # jax maps the variable itself (at import); this code sets nothing
    assert jax.config.jax_compilation_cache_dir == cache_config


def test_env_unset_uses_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert jax_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_stable_across_processes_and_never_on_import():
    """Two fresh processes name the same directory (it is part of the
    cache key), and importing the package sets no cache."""
    code = ("import jax, apex_tpu\n"
            "from apex_tpu.utils.jax_cache import default_cache_dir\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(default_cache_dir())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    outs = [subprocess.run([sys.executable, "-c", code], env=env,
                           cwd="/", capture_output=True, text=True,
                           timeout=120, check=True).stdout.split()
            for _ in range(2)]
    assert outs[0] == outs[1] == ["None", os.path.join(REPO, ".jax_cache")]
