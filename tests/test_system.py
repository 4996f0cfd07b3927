"""End-to-end behaviour tests for the paper's system (slow)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


@pytest.mark.slow
def test_rl_training_loop_runs_and_learns_signal(tmp_path):
    """A short DDPG run must execute, checkpoint, and keep finite losses.

    (Full convergence curves live in EXPERIMENTS.md — trained runs of
    150 episodes; CI checks mechanics, not asymptotics.)
    """
    from repro.launch.rl_train import TrainConfig, train
    cfg = TrainConfig(workload="light", episodes=7, warmup_episodes=2,
                      updates_per_episode=4, hidden=16, max_rq=24,
                      max_jobs=10, periods=10, batch_size=8,
                      batch_episodes=4, eval_every=100, outdir=str(tmp_path))
    out = train(cfg, log_fn=lambda *_: None)
    h = out["history"]                      # one record per collection round
    assert sum(r["batch_episodes"] for r in h) == 7
    assert h[-1]["episode"] == 6
    assert all(np.isfinite(r["sla"]) for r in h)
    assert any("critic_loss" in r for r in h)
    assert os.path.isdir(os.path.join(str(tmp_path), "ckpt"))


@pytest.mark.slow
def test_rl_training_resumes_after_crash(tmp_path):
    """--fail-at crashes the driver; a rerun auto-resumes from ckpt."""
    args = ["--workload", "light", "--episodes", "6", "--hidden", "8",
            "--max-rq", "16", "--max-jobs", "8", "--periods", "6",
            "--warmup-episodes", "99", "--ckpt-every", "2",
            "--eval-every", "100", "--batch-episodes", "2",
            "--outdir", str(tmp_path / "run")]
    r1 = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train", *args,
         "--fail-at", "4"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=540)
    assert r1.returncode != 0                       # crashed as injected
    r2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train", *args],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=540)
    assert r2.returncode == 0, r2.stdout[-1500:] + r2.stderr[-1500:]
    assert "[resume] restored checkpoint" in r2.stdout


@pytest.mark.slow
def test_serve_driver_lm_tenants():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--workload",
         "lm_light", "--policy", "fcfs", "--episodes", "1", "--periods",
         "16", "--max-rq", "48", "--max-jobs", "16"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    # LM tenants serve whole requests through the batched path
    out = json.loads(r.stdout.strip().splitlines()[-1])
    for k in ("sla_rate", "ttft_rate", "tpot_rate"):
        assert 0.0 <= out[k] <= 1.0
