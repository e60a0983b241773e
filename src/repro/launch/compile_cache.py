"""Where JAX's persistent compilation cache lives for this repository.

A cold compile of the full-width round program takes tens of seconds on the
chip, so the entry points (``repro.launch.train``, ``repro.launch.serve``,
``benchmarks.run`` and ``chip_smoke.py``) keep compiled programs on disk.
``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins: the
code then sets no other directory. Otherwise the cache goes to the fixed,
gitignored ``.jax_cache`` at the root of the checkout. The path is part of
every entry's key, so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compilation_cache() -> None:
    """Turn the persistent compilation cache on (JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself when it is set)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
