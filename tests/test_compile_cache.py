"""Where compiled programs are cached (repro.launch.compile_cache): in
``$JAX_COMPILATION_CACHE_DIR`` when it is set, and nowhere else;
otherwise at the fixed ``<checkout>/.jax_cache/``."""

import os
import subprocess
import sys
import time

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a program no other test compiles, under a name the cache entries carry
CODE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print("CACHE_DIR", enable_compile_cache())
def compile_cache_probe(x):
    return jnp.sin(x) * {salt}
jax.jit(compile_cache_probe)(jnp.ones(8)).block_until_ready()
"""


def _compile(env_dir, salt):
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    env.pop(compile_cache.ENV, None)
    if env_dir is not None:
        env[compile_cache.ENV] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CODE.format(salt=salt)],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split("CACHE_DIR ", 1)[1].split()[0]


def _probes(path):
    """The probe's cache entries under ``path`` (other tests' processes
    may be writing their own entries there meanwhile)."""
    if not os.path.isdir(path):
        return set()
    return {e for e in os.listdir(path) if "compile_cache_probe" in e}


def test_env_dir_wins_and_nothing_lands_in_the_checkout(tmp_path):
    before = _probes(compile_cache.CHECKOUT_CACHE_DIR)
    salt = float(time.time_ns())    # a program not cached before
    assert _compile(tmp_path, salt) == str(tmp_path)
    assert _probes(tmp_path)
    assert _probes(compile_cache.CHECKOUT_CACHE_DIR) == before


def test_default_is_the_checkout_cache():
    assert compile_cache.CHECKOUT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    before = _probes(compile_cache.CHECKOUT_CACHE_DIR)
    salt = float(time.time_ns())
    assert _compile(None, salt) == compile_cache.CHECKOUT_CACHE_DIR
    assert _probes(compile_cache.CHECKOUT_CACHE_DIR) - before
