"""The serving check passes the program and fails its control and each
planted fault, at a size a CPU test run holds (4 streams, 30 ticks).

The run goes through ``run.run_cell``, everything ``run.py`` does after
its look for a chip. On the CPU the program's float32 matmuls are exact,
so the configuration states that precision here (``operands`` exact)."""
import time

import pytest

import faults
import run
import serve_cell

SEED = 2**31 + 77


def _small(cell):
    bench, c, cfg, traffic = run.cell_spec(cell)
    return bench, c, dict(cfg, streams=4, operands="exact"), dict(
        traffic, session_ticks=30)


def _run(cell="serve-light-steady"):
    bench, c, cfg, traffic = _small(cell)
    return run.run_cell(bench, c, cfg, traffic, SEED, 0.0, 0,
                        time.perf_counter())


@pytest.mark.parametrize("cell", ["serve-light-steady", "serve-heavy-burst"])
def test_program_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "tick_p95_us",
                                   "serve_periods_per_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(fault):
    with faults.FAULTS[fault]():
        res = _run()
    assert not res["correct"], (fault, res["checks"])


@pytest.mark.parametrize("cell", ["serve-light-steady", "serve-heavy-burst"])
def test_control_is_not_correct(cell):
    _, _, cfg, traffic = _small(cell)
    c = serve_cell.Cell(cfg, traffic, SEED)
    c.warm(False)
    results, _ = c.window(0.0)
    nums = c.check(results, control=cfg["control"])
    assert nums["actor_gap"] > serve_cell.LIMITS["actor_gap"], nums
