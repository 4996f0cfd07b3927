"""Multi-tenant serving plane.

RELMAS (or a baseline) schedules per-layer sub-jobs of tenant requests
onto the simulated heterogeneous MAS.  Two paths: the device-resident
batched one — a fixed-capacity on-device request queue
(``serving.queue``) advanced by ONE jitted scheduling tick per period
across all streams (``repro.core.serve``), fed by the
``serving.loadgen`` scenario load generator — and the per-period
host-loop reference it is measured and parity-tested against
(``serving.service``).
"""
from repro.serving.request import Request, resolve_request
from repro.serving.loadgen import (LoadGenConfig, request_stream,
                                   request_streams, requests_to_trace,
                                   trace_to_requests)
from repro.serving.queue import (pack_admissions, queue_admit, queue_init,
                                 queue_metrics, queue_retire)
from repro.serving.service import MultiTenantService, per_tenant_metrics

__all__ = ["Request", "resolve_request", "LoadGenConfig", "request_stream",
           "request_streams", "requests_to_trace", "trace_to_requests",
           "pack_admissions",
           "queue_admit", "queue_init", "queue_metrics", "queue_retire",
           "MultiTenantService", "per_tenant_metrics"]
