"""The benchmark's copies draw what the program's originals draw."""
import numpy as np
import pytest

import loadgen
import reference as ref
import run

CELLS = ["serve-light-steady", "serve-heavy-burst"]
SEEDS = [[2**31 + 12345, 0], [2**31 + 12345, 3], [7, 1]]


def _program_env(cfg, traffic):
    from repro.sim.arrivals import ArrivalConfig
    from repro.sim.env import EnvConfig, SchedulingEnv
    from repro.workloads.cnn_zoo import build_registry
    reg = build_registry(cfg["workload"], mas=cfg["fleet"])
    ecfg = EnvConfig(t_s_us=cfg["t_s_us"], max_rq=cfg["max_rq"],
                     max_jobs=cfg["max_jobs"])
    arr = ArrivalConfig(max_jobs=cfg["max_jobs"], load=cfg["load"],
                        eff_parallelism=cfg["eff_parallelism"],
                        qos_factor=cfg["qos_factor"],
                        qos_level=cfg["qos_level"],
                        horizon_us=ecfg.horizon_us, slack_us=cfg["slack_us"],
                        scenario=traffic["scenario"])
    return SchedulingEnv(reg, ecfg, arr)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_streams_match_program_loadgen(cell, seed):
    from repro.serving import LoadGenConfig, request_streams
    _, _, cfg, traffic = run.cell_spec(cell)
    env = _program_env(cfg, traffic)
    n = loadgen.requests_per_stream(cfg, traffic)
    lg = LoadGenConfig(scenario=traffic["scenario"],
                       rate_scale=traffic["rate_scale"], n_requests=n)
    want = request_streams(env, lg, 5, seed=seed)
    got = loadgen.streams(cfg, traffic, seed, 5)
    for w, g in zip(want, got):
        assert [r.tenant for r in w] == [cfg["tenants"][m]
                                         for m in g["model"]]
        assert [r.arrival_us for r in w] == g["arrival"].tolist()
        assert [r.deadline_us for r in w] == g["deadline"].tolist()
        assert [r.q_us for r in w] == [float(x) for x in g["q"]]


def test_requests_per_session():
    # the light rate is 2.84 requests/ms per stream, the heavy 0.151
    counts = {c: loadgen.requests_per_stream(*run.cell_spec(c)[2:])
              for c in CELLS}
    assert counts == {"serve-light-steady": 568, "serve-heavy-burst": 31}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_oracle_matches_program_oracle(seed):
    from repro.sim.engine import simulate_np
    rng = np.random.default_rng(seed)
    n, M = 48, 6
    for _ in range(20):
        valid = rng.random(n) < 0.8
        dep = np.where(rng.random(n) < 0.5, np.arange(n) - 1, -1)
        args = (valid, rng.integers(0, M, n), rng.uniform(-1, 1, n),
                rng.uniform(1, 400, n), rng.uniform(0, 12, n), dep,
                rng.uniform(0, 300, n), rng.uniform(0, 200, M), 16.0)
        a, b = ref.simulate_np(*args), simulate_np(*args)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
