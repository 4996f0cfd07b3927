"""Profiler hooks: ``jax.profiler`` trace capture and host spans.

``--profile-dir PATH`` on ``launch/rl_train.py`` / ``launch/serve.py``
wraps the hot loop in :func:`profile_trace`; the captured TensorBoard /
Perfetto trace is readable because the round body, rollout scan, DDPG
update, serving tick and the env's period (``env.slots``, ``env.act``,
``env.engine``) are annotated with ``jax.named_scope`` (see
``repro.core.train`` / ``repro.core.serve`` / ``repro.sim.env`` and
docs/OBSERVABILITY.md "Reading a trace"), and because the host loop
opens :func:`trace_span` spans on the profiler's clock, the device's.
"""
from __future__ import annotations

import contextlib

import jax


def profile_trace(profile_dir: str | None):
    """Context manager capturing a ``jax.profiler`` trace into
    ``profile_dir``; a falsy dir is a no-op (the zero-overhead default,
    so drivers can wrap their loop unconditionally)."""
    if not profile_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(profile_dir)


def trace_span(name: str, **ids):
    """A host span on the profiler's clock: a ``jax.profiler``
    ``TraceAnnotation`` named ``name`` with ``ids`` as its stats, or a
    ``StepTraceAnnotation`` where ``ids`` holds a ``step_num``.  When no
    profiler runs it costs one context manager (about a microsecond)."""
    if "step_num" in ids:
        return jax.profiler.StepTraceAnnotation(name, **ids)
    return jax.profiler.TraceAnnotation(name, **ids)
