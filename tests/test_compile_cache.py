"""Where JAX's persistent compilation cache lands (repro.launch.compile_cache).

Each case compiles in a fresh interpreter so the process-wide cache state of
the test worker is never touched."""
import os

from conftest import REPO, run_in_subprocess

from repro.launch import compile_cache

_CHILD = """
import os, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.launch import compile_cache as cc
cc.DEFAULT_DIR = {default!r}
print("DIR", cc.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(7)).block_until_ready()
"""


def _entries(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_default_cache_dir_is_fixed_inside_the_checkout():
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")


def test_cache_lands_only_where_the_environment_says(tmp_path,
                                                     monkeypatch):
    env_dir, default = tmp_path / "env", tmp_path / "default"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    out = run_in_subprocess(_CHILD.format(default=str(default)), devices=1)
    assert f"DIR {env_dir}" in out
    assert _entries(env_dir) and not _entries(default)


def test_cache_falls_back_to_the_fixed_dir(tmp_path, monkeypatch):
    default = tmp_path / "default"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    out = run_in_subprocess(_CHILD.format(default=str(default)), devices=1)
    assert f"DIR {default}" in out
    assert _entries(default)
