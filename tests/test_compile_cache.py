"""The entry points' persistent compilation cache location."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_is_left_to_jax(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    got = compile_cache.enable_compile_cache()
    assert got == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # the same path on every call: the directory is part of each key
    assert compile_cache.enable_compile_cache() == got


def test_cache_dir_is_git_ignored():
    root = Path(__file__).resolve().parents[1]
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_importing_the_package_sets_no_cache_dir():
    code = ("import jax, repro, repro.serving, repro.kernels.ops; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "None"
