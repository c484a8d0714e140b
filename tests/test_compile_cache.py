"""The entry points' persistent compilation cache goes where it should.

Each case runs in a fresh interpreter: JAX sets its cache up once per
process, and the pytest process must keep its own configuration.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_PROBE = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    if sys.argv[1] == "compile":
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
    print(path)
""")


def _probe(env_dir, action):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _PROBE, action], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip()


@pytest.mark.parametrize("set_by_env", [True, False])
def test_compile_cache_directory(tmp_path, set_by_env):
    """With JAX_COMPILATION_CACHE_DIR set, the cache is written there and
    nowhere else; without it, it is the checkout's fixed .jax_cache."""
    if set_by_env:
        cache = tmp_path / "cache"
        assert _probe(str(cache), "compile") == str(cache)
        # even sub-second compiles are kept
        assert any(p.name.endswith("-cache") for p in cache.iterdir())
    else:
        assert _probe(None, "none") == os.path.join(ROOT, ".jax_cache")
