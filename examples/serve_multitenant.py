"""End-to-end multi-tenant serving of whole LM requests.

RELMAS (a trained checkpoint if one is on disk, else an untrained
actor) schedules per-layer sub-jobs of LM tenant requests onto the
simulated heterogeneous MAS: each request is a prefill, then one decode
pass per output token.  Prints SLA, TTFT and TPOT attainment and the
SLA per tenant.

Run:  PYTHONPATH=src python examples/serve_multitenant.py
"""
import os

from repro.serving import LoadGenConfig, MultiTenantService
from repro.serving.loadgen import request_streams
from repro.sim.arrivals import ArrivalConfig
from repro.sim.env import EnvConfig
from repro.workloads import build_llm_registry

print("=== RELMAS over LM tenants on the simulated MAS ===")
registry = build_llm_registry("lm_light")
ecfg = EnvConfig(t_s_us=2000.0, max_rq=48, max_jobs=24)
ckpt = os.path.join("runs", "light_medium", "best")
svc = MultiTenantService(registry, policy="relmas",
                         ckpt_dir=ckpt if os.path.isdir(ckpt) else None,
                         env_cfg=ecfg, arrivals=ArrivalConfig(max_jobs=24,
                                                              load=0.8))
streams = request_streams(svc.env, LoadGenConfig(n_requests=6,
                                                 out_median=16.0), 4, seed=7)
res = svc.serve_stream(streams, ticks=300)
agg = res["aggregate"]
print(f"SLA (both limits) {agg['sla_rate']:.3f}, TTFT {agg['ttft_rate']:.3f}, "
      f"TPOT {agg['tpot_rate']:.3f} ({agg['counted']} requests, "
      f"{agg['energy_uj'] / 1e6:.2f} J)")
for tenant, tm in res["metrics"][0]["per_tenant"].items():
    if tm["jobs"]:
        print(f"  {tenant:>16s}: jobs={tm['jobs']:3d} sla={tm['sla_rate']:.3f}")
