"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache.

The config writes are captured, not applied: tests never turn the cache on.
"""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def updates(monkeypatch):
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    return seen


def test_env_dir_is_used_and_no_other_set(monkeypatch, updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_enable_compilation_cache"] is True


def test_default_dir_is_fixed_under_the_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = compile_cache.REPO_ROOT
    assert path == str(root / ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == path
    assert (root / "chip_smoke.py").is_file()
    assert (root / "src" / "repro").is_dir()


def test_disabled_cache_stays_off():
    """JAX_ENABLE_COMPILATION_CACHE=false, as the launcher subprocess tests
    set it, leaves the cache off and places no directory. JAX reads the
    variable once, at import, hence the fresh process."""
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=str(compile_cache.REPO_ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from repro.launch.compile_cache import enable_compile_cache\n"
         "print(enable_compile_cache(), jax.config.jax_enable_compilation_cache,"
         " jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["None", "False", "None"]
