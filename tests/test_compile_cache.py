"""``repro.launch.cache``: the persistent compilation cache's one place."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax

from repro.launch import cache

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        got = cache.enable_compile_cache()
        assert got == str(cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert cache.DEFAULT_DIR == repo / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_receives_the_cache(tmp_path):
    """With the variable set, a compile lands in that directory and the
    helper sets nothing itself."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.cache import enable_compile_cache
        before = jax.config.jax_compilation_cache_dir
        got = enable_compile_cache()
        assert got == before == jax.config.jax_compilation_cache_dir, got
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
        print(got)
    """)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry written"
