"""The persistent compilation cache lands where the entry points put it:
``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed path in the
checkout (launch/compile_cache.py). Each case runs in a fresh process,
since the cache directory is fixed at a process's first compile."""
import json
import os
import subprocess
import sys

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

PROBE = r"""
import json, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(5)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env, mode):
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu", **env}
    env = {k: v for k, v in env.items() if v is not None}
    out = subprocess.run([sys.executable, "-c", PROBE, mode], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_cache_goes_to_env_dir_and_nowhere_else(tmp_path):
    cache = tmp_path / "cache"
    before = _listing(CHECKOUT_CACHE_DIR)
    got = _probe({"JAX_COMPILATION_CACHE_DIR": str(cache)}, "compile")
    assert got == {"path": str(cache), "config": str(cache)}
    assert _listing(cache), "no compiled entry written"
    assert _listing(CHECKOUT_CACHE_DIR) == before


def test_cache_defaults_to_fixed_checkout_path():
    got = _probe({"JAX_COMPILATION_CACHE_DIR": None}, "config-only")
    assert got == {"path": CHECKOUT_CACHE_DIR, "config": CHECKOUT_CACHE_DIR}
    checkout = os.path.dirname(SRC)
    assert CHECKOUT_CACHE_DIR == os.path.join(checkout, ".jax_cache")
