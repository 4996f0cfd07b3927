"""Serving plane: the scheduling service over CNN and LM tenants."""
from repro.serving import MultiTenantService
from repro.sim.env import EnvConfig
from repro.workloads import build_registry, build_llm_registry


def test_service_baseline_episode():
    svc = MultiTenantService(build_registry("light"), policy="fcfs",
                             env_cfg=EnvConfig(periods=10, max_rq=32,
                                               max_jobs=12))
    m = svc.run_episode(seed=0)
    assert 0.0 <= m["sla_rate"] <= 1.0
    assert set(m["per_tenant"]) == {"squeezenet", "yolo_lite",
                                    "keyword_spotting"}


def test_service_lm_tenants():
    svc = MultiTenantService(
        build_llm_registry("lm_light"), policy="herald",
        env_cfg=EnvConfig(periods=8, max_rq=32, max_jobs=8,
                          t_s_us=2000.0, bandwidth_gbps=819.0))
    m = svc.run_episode(seed=1)
    assert 0.0 <= m["sla_rate"] <= 1.0
    assert "mamba2-2.7b" in m["per_tenant"]
