"""Compile counters: what tracing, lowering and compiling cost a process.

:func:`install_compile_counter` registers one listener on JAX's
monitoring events (once per process; later calls do nothing) that keeps
cumulative seconds and counts of

- ``trace``:   ``/jax/core/compile/jaxpr_trace_duration``
- ``lower``:   ``/jax/core/compile/jaxpr_to_mlir_module_duration``
- ``compile``: ``/jax/core/compile/backend_compile_duration``

and the persistent compilation cache's ``/jax/compilation_cache/
cache_hits`` and ``cache_misses``.  :func:`compile_counts` snapshots
them; the drivers put the snapshot in their ``run_end`` record.  JAX's
listeners are process-wide and cannot be taken back, so the counts are
too: a caller that wants its own share passes an earlier snapshot as
``since``.
"""
from __future__ import annotations

import threading

import jax

_DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}

_lock = threading.Lock()
_counts: dict[str, float] = {}
_installed = False


def _zero() -> dict[str, float]:
    out: dict[str, float] = {}
    for k in _DURATIONS.values():
        out[k + "_s"] = 0.0
        out[k + "_n"] = 0
    for k in _EVENTS.values():
        out[k] = 0
    return out


def _on_duration(event: str, secs: float, **_) -> None:
    k = _DURATIONS.get(event)
    if k is not None:
        with _lock:
            _counts[k + "_s"] += secs
            _counts[k + "_n"] += 1


def _on_event(event: str, **_) -> None:
    k = _EVENTS.get(event)
    if k is not None:
        with _lock:
            _counts[k] += 1


def install_compile_counter() -> None:
    """Start counting (idempotent: the listener is registered once)."""
    global _installed
    with _lock:
        if _installed:
            return
        _counts.update(_zero())
        _installed = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def compile_counts(since: dict | None = None) -> dict[str, float]:
    """Cumulative ``{trace,lower,compile}_{s,n}``, ``cache_hits`` and
    ``cache_misses`` since :func:`install_compile_counter` (all zero
    before it), or since the snapshot ``since`` where one is given."""
    with _lock:
        now = dict(_counts) if _installed else _zero()
    if since is not None:
        now = {k: v - since.get(k, 0) for k, v in now.items()}
    return {k: round(v, 6) + 0.0 if isinstance(v, float) else v
            for k, v in now.items()}
