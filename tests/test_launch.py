"""Launch helpers: the agent mesh refuses to fold agents onto fewer
devices than asked for, and the persistent compile cache lands where
``JAX_COMPILATION_CACHE_DIR`` says, else in the fixed in-checkout path."""
import os
import subprocess
import sys
import time

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.mesh import make_agent_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one compile above the cache's time floor, then report the dir; the
# salt makes the program (so its cache key) new on every test run
_COMPILE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "d = enable_compile_cache()\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
    "jax.jit(lambda x: jnp.sin(x) * {salt!r})(jnp.ones(7))"
    ".block_until_ready()\n"
    "print(d)\n")


def test_agent_mesh_refuses_more_positions_than_devices():
    n = len(jax.devices())
    assert dict(make_agent_mesh().shape) == {"agents": n}
    with pytest.raises(ValueError, match=f"{n} cpu device"):
        make_agent_mesh(n + 1)


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def _run(env, salt):
    env = dict(env, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c",
                           _COMPILE.format(salt=salt)], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1]


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    salt = float(time.time_ns() % 1_000_003)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    placed = tmp_path / "cache"
    before = _entries(compile_cache.DEFAULT_DIR)
    assert _run(dict(env, JAX_COMPILATION_CACHE_DIR=str(placed)),
                salt) == str(placed)
    assert _entries(placed)
    assert _entries(compile_cache.DEFAULT_DIR) == before

    assert _run(env, salt + 1) == str(compile_cache.DEFAULT_DIR)
    assert _entries(compile_cache.DEFAULT_DIR) - before
