"""Where the persistent compilation cache lands."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_lands_in_the_environment_directory(tmp_path):
    src = ("import jax, jax.numpy as jnp\n"
           "from repro.launch import compile_cache\n"
           "print(compile_cache.enable())\n"
           "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
           " 0)\n"
           "jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", src], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)
    assert any(n.endswith("-cache") for n in os.listdir(tmp_path))


def test_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
