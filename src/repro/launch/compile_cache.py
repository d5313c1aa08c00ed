"""Where JAX's persistent compilation cache lives, for every entry point.

A fresh process compiles every program it runs; a full-width step program
takes tens of seconds. The entry points (``launch/train.py``,
``launch/serve_agg.py``, ``launch/sweep.py``, ``exec/worker.py``,
``chip_smoke.py``) call ``enable_compile_cache()`` before their first
compile so that later processes find those programs again:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  keeps the cache there; this module sets no other directory.
* unset: the cache goes to ``<checkout>/.jax_cache``, one fixed path (it
  is part of each entry's key, so a directory that moves never hits).

JAX's own thresholds decide what is worth keeping (programs that compile
in under a second are not written).
"""
from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
