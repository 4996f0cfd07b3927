"""End-to-end multi-tenant serving: control plane + data plane.

The paper's deployment scenario, both halves live:

- CONTROL PLANE: RELMAS (trained checkpoint if available, else the
  min-finish heuristic) schedules per-layer sub-jobs of LM tenant
  requests onto the simulated heterogeneous MAS; we report SLA
  satisfaction per tenant.

- DATA PLANE: a real (small) JAX LM serves the same request stream with
  batched prefill + continuously-batched decode — proving the serving
  path (KV caches, slot reuse, greedy sampling) end to end on actual
  compute.

Run:  PYTHONPATH=src python examples/serve_multitenant.py
"""
import os
import time

import jax
import numpy as np

from repro.configs.registry import get_arch
from repro.models.model import build_model, param_count
from repro.serving import ContinuousBatcher, LoadGenConfig, \
    MultiTenantService, synth_requests
from repro.serving.loadgen import request_streams
from repro.sim.arrivals import ArrivalConfig
from repro.sim.env import EnvConfig
from repro.workloads import build_llm_registry

# ---------------------------------------------------------------- control
print("=== control plane: RELMAS over LM tenants on the simulated MAS ===")
# whole requests: a prefill, then one decode pass per output token
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

# ------------------------------------------------------------------ data
print("\n=== data plane: real model, batched requests ===")
cfg = get_arch("internlm2-1.8b", smoke=True)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
print(f"serving {cfg.name} ({param_count(params):,} params), "
      f"4 slots, greedy decode")
batcher = ContinuousBatcher(model, params, n_slots=4, smax=128)
reqs = synth_requests(["internlm2-smoke"], n=10, horizon_us=500.0,
                      qos_budget_us={"internlm2-smoke": 1e9},
                      vocab=cfg.vocab, prompt_len=8, max_new=12, seed=1)
pending, done = list(reqs), []
t0 = time.time()
steps = 0
while pending or batcher.active():
    while pending and batcher.has_free_slot():
        batcher.add(pending.pop(0))
    done += batcher.step()
    steps += 1
dt = time.time() - t0
total_toks = sum(len(r.tokens_out) for r in done)
print(f"served {len(done)} requests / {total_toks} tokens in {dt:.2f}s "
      f"({steps} batched decode steps, "
      f"{total_toks / max(dt, 1e-9):.0f} tok/s on CPU)")
print("sample output ids:", done[0].tokens_out)
