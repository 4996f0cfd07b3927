"""Telemetry plane: device-resident metrics, JSONL sinks, profiler hooks.

Two halves with a deliberate boundary:

- **In-graph** (``repro.telemetry.metrics``): counters and fixed-bucket
  histograms as pure pytree reducers that live inside the
  fused training round and the serving tick — accumulated on device,
  bit-neutral to every existing output, crossing the host boundary
  only in the transfers the programs already make (per training chunk,
  per serving tick).
- **Host-side** (``repro.telemetry.sink`` / ``schema`` / ``console`` /
  ``runmeta`` / ``profiler`` / ``compiles``): a :class:`Telemetry`
  session validates schema'd records and streams them to console /
  JSONL / null backends, times host sections as ``span`` records,
  stamps run provenance (git SHA, ISO timestamp, jax identity), and
  gates ``jax.profiler`` trace capture; :func:`trace_span` opens host
  spans on the profiler's clock, and the compile counter keeps what
  tracing, lowering and compiling cost the process.

See docs/OBSERVABILITY.md for schemas and usage;
``scripts/metrics_summary.py`` renders/validates the JSONL streams.
"""
from repro.telemetry.console import console_line, format_record
from repro.telemetry.compiles import compile_counts, install_compile_counter
from repro.telemetry.metrics import (REWARD_EDGES, ROUND_TELE_COUNTS,
                                     ROUND_TELE_GAUGES, ROUND_TELE_KEYS,
                                     SLA_EDGES, counter_add, counter_init,
                                     hist_add, hist_init, round_telemetry)
from repro.telemetry.profiler import profile_trace, trace_span
from repro.telemetry.runmeta import git_sha, iso_now, run_meta
from repro.telemetry.schema import (SCHEMA_VERSION, SCHEMAS, SchemaError,
                                    validate_record)
from repro.telemetry.sink import (ConsoleSink, JsonlSink, ListSink,
                                  MetricsSink, NullSink, Telemetry,
                                  make_telemetry, null_telemetry)

__all__ = [
    "SCHEMA_VERSION", "SCHEMAS", "SchemaError", "validate_record",
    "SLA_EDGES", "REWARD_EDGES", "ROUND_TELE_COUNTS", "ROUND_TELE_GAUGES",
    "ROUND_TELE_KEYS", "counter_init", "counter_add", "hist_init",
    "hist_add", "round_telemetry", "console_line", "format_record",
    "git_sha", "iso_now", "run_meta", "profile_trace", "trace_span",
    "install_compile_counter", "compile_counts", "MetricsSink",
    "NullSink", "JsonlSink", "ConsoleSink", "ListSink", "Telemetry",
    "make_telemetry", "null_telemetry",
]
