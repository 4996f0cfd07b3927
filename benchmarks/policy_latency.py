"""Scheduler-overhead microbenchmark: wall-time + MACs per invocation.

The paper's viability argument (Sec. 5.3): the policy is ~0.04% of an
AlexNet per RQ layer.  We measure the jitted end-to-end invocation
latency on this host and reproduce the MAC accounting.

:func:`run_serving` extends the accounting to the two serving
dispatches: the legacy per-period host-loop call (one policy + sim
dispatch per stream per period — how requests were scheduled before the
batched path) vs the single-dispatch serving tick
(``repro.core.serve.make_serving_tick``: admission + policy + sim +
retire for ALL streams in one call), reporting the per-stream amortized
cost of each.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy as P
from repro.launch.compile_cache import use_compile_cache

ALEXNET_MACS = 714_188_480     # conv+fc MACs of AlexNet-227


def run(*, hidden: int = 256, rq: int = 96, iters: int = 30) -> dict:
    pcfg = P.PolicyConfig(feat_dim=16, act_dim=7, hidden=hidden)
    params = P.init_actor(jax.random.PRNGKey(0), pcfg)
    feats = jax.random.normal(jax.random.PRNGKey(1), (rq + 1, 16))
    mask = jnp.ones((rq + 1,), bool)
    fn = jax.jit(lambda p, f, m: P.actor_apply(p, pcfg, f, m))
    fn(params, feats, mask).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(params, feats, mask).block_until_ready()
    us = (time.perf_counter() - t0) / iters * 1e6
    macs = P.actor_macs_per_timestep(pcfg)
    frac = macs / ALEXNET_MACS
    print(f"policy_latency,hidden={hidden},rq={rq},us_per_call={us:.1f},"
          f"macs_per_step={macs},frac_of_alexnet={frac * 100:.4f}%",
          flush=True)
    return {"us_per_call": us, "macs_per_timestep": macs,
            "frac_of_alexnet": frac}


def run_serving(*, streams: int = 8, periods: int = 20, max_rq: int = 32,
                max_jobs: int = 16, iters: int = 20, seed: int = 0) -> dict:
    """Per-dispatch latency of the two serving paths.

    ``legacy_period_us``: one blocking ``_period`` dispatch (the host
    loop pays this once per stream per period).  ``tick_us``: one
    batched serving tick (all ``streams`` advanced a period in one
    dispatch); ``tick_per_stream_us`` is its amortized per-stream cost —
    the number to compare against ``legacy_period_us``.
    """
    from repro.serving import (LoadGenConfig, MultiTenantService,
                               request_streams)
    from repro.sim.env import EnvConfig
    from repro.workloads import build_registry
    svc = MultiTenantService(build_registry("light"), policy="relmas",
                             env_cfg=EnvConfig(periods=periods,
                                               max_rq=max_rq,
                                               max_jobs=max_jobs))
    # legacy arm: per-period dispatch, blocking
    trace, state = svc.env.new_episode(np.random.default_rng(seed))
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    state, _, _ = svc._period(svc.params, state, trace, sub, sigma=0.0)
    jax.block_until_ready(state["t"])                    # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        key, sub = jax.random.split(key)
        state, _, _ = svc._period(svc.params, state, trace, sub, sigma=0.0)
        jax.block_until_ready(state["t"])
    legacy_us = (time.perf_counter() - t0) / iters * 1e6
    # batched arm: serve loadgen traffic, read the tick wall times the
    # serving loop records around its one dispatch per period
    lg = LoadGenConfig(scenario="steady", n_requests=16)
    reqs = request_streams(svc.env, lg, streams, seed=seed)
    svc.serve_stream(reqs, tick_k=max_jobs, seed=seed)   # warmup/compile
    res = svc.serve_stream(reqs, tick_k=max_jobs, seed=seed + 1)
    tick_us = float(np.median(res["stats"]["tick_wall_us"]))
    out = {"streams": streams, "legacy_period_us": round(legacy_us, 1),
           "tick_us": round(tick_us, 1),
           "tick_per_stream_us": round(tick_us / streams, 1),
           "dispatch_amortization": round(legacy_us * streams / tick_us, 2)}
    print(f"serving_dispatch,streams={streams},"
          f"legacy_period_us={out['legacy_period_us']},"
          f"tick_us={out['tick_us']},"
          f"tick_per_stream_us={out['tick_per_stream_us']},"
          f"amortization={out['dispatch_amortization']}x", flush=True)
    return out


def main():
    use_compile_cache()
    run()
    run_serving()


if __name__ == "__main__":
    main()
