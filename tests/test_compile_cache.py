"""Where entry points keep JAX's persistent compilation cache
(src/repro/launch/cache.py): ``$JAX_COMPILATION_CACHE_DIR`` when set,
the fixed in-checkout directory otherwise, and nothing at import."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import cache

REPO = Path(__file__).resolve().parents[1]


def test_default_dir_is_fixed_inside_the_checkout():
    assert cache.DEFAULT_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache.cache_dir() == str(cache.DEFAULT_DIR)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.cache_dir() == str(tmp_path)


@pytest.mark.parametrize("from_env", [True, False])
def test_compiles_land_in_the_cache_dir(tmp_path, from_env):
    """A child process jits one function after ``enable_compile_cache``;
    the compiled program must appear in the expected directory. Without
    the variable the child points DEFAULT_DIR at a scratch directory so
    the test writes nothing into the checkout."""
    target = tmp_path / "cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + env.get("PYTHONPATH", ""))
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(target)
    script = textwrap.dedent(f"""
        import pathlib
        import jax, jax.numpy as jnp
        from repro.launch import cache
        assert not jax.config.jax_compilation_cache_dir or {from_env}
        if not {from_env}:
            cache.DEFAULT_DIR = pathlib.Path({str(target)!r})
        print(cache.enable_compile_cache())
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(16)).block_until_ready()
    """)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(target)
    assert any(target.iterdir()), "no compiled program was cached"
