"""The specialist's batched serving tick against its host loop, on
every fleet preset and CNN tenant set: one trace served both ways
retires the same SLA, energy and per-tenant numbers, bit for bit."""
import numpy as np
import pytest

from repro.costmodel.fleets import fleet_names
from repro.serving import MultiTenantService, trace_to_requests
from repro.sim.env import EnvConfig
from repro.workloads import build_registry
from repro.workloads.cnn_zoo import WORKLOADS

CFG = EnvConfig(periods=10, max_rq=32, max_jobs=12)


@pytest.mark.parametrize("workload", sorted(WORKLOADS))
@pytest.mark.parametrize("fleet", fleet_names())
def test_specialist_parity_host_vs_batched(fleet, workload, assert_parity):
    svc = MultiTenantService(build_registry(workload, mas=fleet),
                             policy="relmas", env_cfg=CFG)
    trace, _ = svc.env.new_episode(np.random.default_rng(0))
    ref = svc.serve_trace_host(trace, seed=0)
    out = svc.serve_stream(trace_to_requests(svc.env, trace),
                           tick_k=CFG.max_jobs, seed=0)
    assert_parity(ref, out["metrics"][0])
