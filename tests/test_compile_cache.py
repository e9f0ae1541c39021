"""The launchers' persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
is set, JAX's one cache directory is that one and entries land there;
unset, it is <checkout>/.jax_cache. Each case compiles in a fresh CPU
subprocess (JAX reads the variable when it is imported)."""
import os
import subprocess
import sys
import time

from repro.launch.compile_cache import CHECKOUT

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * {salt!r})(jnp.ones(7)).block_until_ready()
print(path, jax.config.jax_compilation_cache_dir)
"""


def _run(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    salt = float(time.time_ns() % 1_000_003)  # a program no run has cached
    r = subprocess.run([sys.executable, "-c", CODE.format(salt=salt)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip().splitlines()[-1].split()


def _entries(d):
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def test_env_dir_is_the_cache(tmp_path):
    assert _run(tmp_path) == [str(tmp_path)] * 2
    assert _entries(tmp_path), "nothing cached in JAX_COMPILATION_CACHE_DIR"


def test_unset_env_caches_in_checkout():
    default = CHECKOUT / ".jax_cache"
    assert str(default).startswith(REPO)
    before = _entries(default)
    assert _run(None) == [str(default)] * 2
    assert _entries(default) - before, "nothing new cached in .jax_cache"
