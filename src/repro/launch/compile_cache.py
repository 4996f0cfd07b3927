"""Persistent XLA compilation cache at a path that can be placed from
outside.

Entry points (``rl_train``, ``serve``, ``chip_smoke.py``, the
``benchmarks`` mains) call :func:`use_compile_cache` before their first
compile; library modules never do, so importing the package (and the
test suite) leaves JAX's cache configuration alone.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it (and the
  other ``JAX_PERSISTENT_CACHE_*`` variables); nothing is changed.
- otherwise the cache goes to ``<repo>/.jax_cache``.  The path is fixed
  (never a temporary name, a PID or a time) so that the next process
  finds what this one wrote.  Every compile is kept,
  not only those over JAX's 1 s default: a training run is dozens of
  sub-second compiles (evals, baselines, the host loop) that together
  cost more than the fused round.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_DIR
