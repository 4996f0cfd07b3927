"""Cross-fleet transfer matrix: how platform-specific is a learned policy?

The ROADMAP's transfer-study item, built on the fleet-conditioned
generalist subsystem (``repro.core.generalist``): every policy below
uses the M-agnostic descriptor-conditioned architecture at one common
``m_max`` — so a checkpoint trained on ANY fleet restores on EVERY
fleet — and three policy rows are trained in-suite (checkpoints in
``runs/`` are machine-local, so the committed artifact must be
self-contained):

- ``generalist``          ONE policy trained on all fleets mixed (a
                          fleet sampled per fused round);
- ``specialist:<fleet>``  the same architecture trained on one fleet
                          only — its off-diagonal cells measure how much
                          platform the weights absorbed;
- ``untrained``           random init — the floor every trained row
                          must clear.

Each row evaluates on each fleet (``fleets x fleets`` for the
specialists) in the calibrated evaluation regime (load/QoS matching
``benchmarks/sweep.py``), one jitted batched eval per cell.

A *churn robustness* section re-scores every learned row — plus
one-shot heuristic reference rows (``heuristic:<name>``, evaluated on
the unpadded per-fleet envs) — under seeded fleet-churn presets
(``repro.sim.churn``): the question is whether the descriptor-
conditioned generalist, which sees per-period validity/degradation in
its conditioning, degrades more gracefully than the specialists and
the heuristics when SAs fail or throttle mid-episode.

Usage:
  PYTHONPATH=src python -m benchmarks.transfer              # quick
  PYTHONPATH=src python -m benchmarks.transfer --full       # paper-sized
  PYTHONPATH=src python -m benchmarks.transfer --smoke      # CI (2x2)
  PYTHONPATH=src python -m benchmarks.transfer --fleets paper6,8simba
  PYTHONPATH=src python -m benchmarks.transfer --churn fail,slowdown

Output: one ``transfer,...`` CSV-ish line per cell + a fleets x fleets
``BENCH_transfer.json`` (cells keyed ``<row>/<eval_fleet>``, churned
cells ``<row>/<eval_fleet>/churn:<preset>``, heuristic references
``heuristic:<name>/<eval_fleet>[...]`` — schema in docs/BENCHMARKS.md)
for regression tracking across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax

from benchmarks.common import (EVAL_LOAD, EVAL_QOS_FACTOR, REPO, bench_meta,
                               make_env)
from repro.ckpt import restore_checkpoint
from repro.core import baselines as BL
from repro.core import policy as P
from repro.core.generalist import (GeneralistSpec, build_padded_envs,
                                   evaluate_generalist_batch)
from repro.core.rollout import evaluate_batch_baseline
from repro.costmodel import get_fleet
from repro.costmodel.fleets import fleet_names
from repro.launch.compile_cache import use_compile_cache
from repro.launch.rl_train import TrainConfig, train
from repro.sim.arrivals import ArrivalConfig
from repro.sim.churn import CHURN_SCENARIOS, churn_preset
from repro.sim.env import EnvConfig

DEFAULT_FLEETS = ("paper6", "8simba", "8eyeriss")

# churn presets for the robustness section (hard capacity loss vs soft
# degradation) and the one-shot reference schedulers scored alongside
# the learned rows
DEFAULT_CHURNS = ("fail", "throttle")
HEURISTICS = ("fcfs", "herald")

# training/eval budgets per grid size:
# (periods, max_rq, max_jobs, hidden, episodes, batch_episodes,
#  updates_per_episode, n_seeds, replay, warmup)
# "quick" is the committed-artifact budget: ~200 episodes at the
# sweep's quick env shape is where every trained row clears the
# untrained floor with margin (shorter budgets demonstrably don't)
SIZES = {
    "full": (60, 96, 64, 64, 300, 8, 30, 8, 4000, 8),
    "quick": (24, 48, 32, 32, 200, 8, 30, 8, 4000, 8),
    "smoke": (8, 16, 8, 8, 4, 2, 2, 2, 64, 2),
}


def _train_row(fleets_csv: str, m_max: int, size: tuple, workload: str,
               outdir: str, seed: int, log_fn) -> tuple:
    """Train one generalist-architecture policy (single- or multi-fleet)
    and return its BEST-eval actor params (periodic eval on the
    training seeds selects the checkpoint; the transfer matrix itself
    is scored on disjoint seeds)."""
    periods, max_rq, max_jobs, hidden, episodes, be, upd, _, replay, \
        warm = size
    cfg = TrainConfig(
        workload=workload, fleet=fleets_csv, policy_kind="generalist",
        m_max=m_max, load=EVAL_LOAD, qos_factor=EVAL_QOS_FACTOR,
        periods=periods, max_rq=max_rq, max_jobs=max_jobs, hidden=hidden,
        episodes=episodes, batch_episodes=be, updates_per_episode=upd,
        batch_size=32 if hidden > 8 else 8, replay_capacity=replay,
        warmup_episodes=warm, eval_every=max(2, episodes // 12),
        eval_seeds=3, ckpt_every=10 ** 9, seed=seed, outdir=outdir,
        # maximin over per-fleet eval SLA: don't let the saved
        # checkpoint trade its weakest platform away for the mean
        best_metric="min_fleet")
    out = train(cfg, log_fn=log_fn)
    params = out["state"].actor
    best_dir = os.path.join(outdir, "best")
    try:
        params, _, _ = restore_checkpoint(best_dir, params)
    except (FileNotFoundError, KeyError, ValueError):
        pass                   # no eval fired (smoke) -> final params
    return params, out["pcfg"], out["spec"]


def run(*, quick: bool = True, smoke: bool = False, workload: str = "light",
        fleets=DEFAULT_FLEETS, churns=DEFAULT_CHURNS,
        out: str | None = None, verbose: bool = False) -> dict:
    size_name = "smoke" if smoke else ("quick" if quick else "full")
    size = SIZES[size_name]
    if smoke and churns is DEFAULT_CHURNS:
        churns = ("fail",)
    bad = [c for c in churns if c == "none" or c not in CHURN_SCENARIOS]
    if bad:
        raise ValueError(f"bad churn preset(s) {bad}; choose from "
                         f"{[c for c in CHURN_SCENARIOS if c != 'none']}")
    periods, max_rq, max_jobs, hidden, episodes, *_ = size
    n_seeds = size[7]
    m_max = max(get_fleet(f).num_sas for f in fleets)
    spec = GeneralistSpec(m_max=m_max)
    seeds = range(7600, 7600 + n_seeds)
    log_fn = print if verbose else (lambda *_: None)

    # eval envs: each fleet padded to the suite's m_max, calibrated
    # regime (in-distribution: _train_row trains at the same load/QoS)
    ecfg = EnvConfig(periods=periods, max_rq=max_rq, max_jobs=max_jobs)
    arr = ArrivalConfig(max_jobs=max_jobs, load=EVAL_LOAD,
                        qos_factor=EVAL_QOS_FACTOR,
                        horizon_us=ecfg.horizon_us,
                        slack_us=2.0 * ecfg.t_s_us)
    eval_envs = dict(zip(fleets, build_padded_envs(
        workload, fleets, ecfg, arr, m_max=m_max)))

    t_all = time.time()
    rows: dict[str, tuple] = {}
    with tempfile.TemporaryDirectory(prefix="relmas_transfer_") as td:
        t0 = time.time()
        params, pcfg, _ = _train_row(",".join(fleets), m_max, size,
                                     workload, os.path.join(td, "gen"),
                                     seed=0, log_fn=log_fn)
        rows["generalist"] = (params, list(fleets),
                              round(time.time() - t0, 1))
        print(f"transfer_train,generalist,{rows['generalist'][2]}s",
              flush=True)
        for i, f in enumerate(fleets):
            t0 = time.time()
            params, _, _ = _train_row(f, m_max, size, workload,
                                      os.path.join(td, f"spec_{f}"),
                                      seed=100 + i, log_fn=log_fn)
            rows[f"specialist:{f}"] = (params, [f],
                                       round(time.time() - t0, 1))
            print(f"transfer_train,specialist:{f},"
                  f"{rows[f'specialist:{f}'][2]}s", flush=True)
    # untrained floor: the same architecture at random init
    rows["untrained"] = (P.init_actor(jax.random.PRNGKey(0), pcfg),
                         [], 0.0)

    cells: dict[str, dict] = {}
    for row, (params, train_fleets, _) in rows.items():
        kind = ("generalist" if row == "generalist"
                else ("untrained" if row == "untrained" else "specialist"))
        for f, env in eval_envs.items():
            for ch in ("none",) + tuple(churns):
                ccfg = None if ch == "none" else churn_preset(ch)
                suf = "" if ch == "none" else f"/churn:{ch}"
                t0 = time.time()
                m = evaluate_generalist_batch(env, pcfg, params, seeds,
                                              churn=ccfg)
                cells[f"{row}/{f}{suf}"] = dict(
                    sla_rate=round(m["sla_rate"], 4),
                    energy_uj=round(m["energy_uj"], 1),
                    policy_kind=kind, train_fleets=train_fleets,
                    wall_s=round(time.time() - t0, 2))
                print(f"transfer,{row},{f},churn={ch},"
                      f"sla={cells[f'{row}/{f}{suf}']['sla_rate']}",
                      flush=True)

    # one-shot heuristic reference rows for the robustness comparison:
    # scored on the UNPADDED per-fleet envs (heuristics are M-agnostic
    # by construction — no padding/descriptors involved)
    heur_envs = {f: make_env(workload, fleet=f, periods=periods,
                             max_rq=max_rq, max_jobs=max_jobs,
                             load=EVAL_LOAD, qos_factor=EVAL_QOS_FACTOR)
                 for f in fleets}
    for h in HEURISTICS:
        for f in fleets:
            henv = heur_envs[f]
            for ch in ("none",) + tuple(churns):
                ccfg = None if ch == "none" else churn_preset(ch)
                suf = "" if ch == "none" else f"/churn:{ch}"
                t0 = time.time()
                m = evaluate_batch_baseline(henv, BL.BASELINES[h], seeds,
                                            churn=ccfg)
                cells[f"heuristic:{h}/{f}{suf}"] = dict(
                    sla_rate=round(m["sla_rate"], 4),
                    energy_uj=round(m["energy_uj"], 1),
                    policy_kind="heuristic", train_fleets=[],
                    wall_s=round(time.time() - t0, 2))
                print(f"transfer,heuristic:{h},{f},churn={ch},"
                      f"sla={cells[f'heuristic:{h}/{f}{suf}']['sla_rate']}",
                      flush=True)

    gen = {f: cells[f"generalist/{f}"]["sla_rate"] for f in fleets}
    unt = {f: cells[f"untrained/{f}"]["sla_rate"] for f in fleets}
    diag = [cells[f"specialist:{f}/{f}"]["sla_rate"] for f in fleets]
    off = [cells[f"specialist:{f}/{g}"]["sla_rate"]
           for f in fleets for g in fleets if f != g]

    def _mean(v):
        return round(sum(v) / len(v), 4)

    # robustness: absolute churned SLA + drop-vs-static per row class
    # (generalist vs on-diagonal specialists vs each heuristic) — the
    # committed generalist-vs-specialist churn comparison
    robustness: dict[str, dict] = {}
    for ch in churns:
        g_ch = [cells[f"generalist/{f}/churn:{ch}"]["sla_rate"]
                for f in fleets]
        s_ch = [cells[f"specialist:{f}/{f}/churn:{ch}"]["sla_rate"]
                for f in fleets]
        entry = {
            "generalist_sla": _mean(g_ch),
            "generalist_drop": _mean([gen[f] - v
                                      for f, v in zip(fleets, g_ch)]),
            "specialist_diag_sla": _mean(s_ch),
            "specialist_diag_drop": _mean([d - v
                                           for d, v in zip(diag, s_ch)]),
        }
        for h in HEURISTICS:
            h_base = [cells[f"heuristic:{h}/{f}"]["sla_rate"]
                      for f in fleets]
            h_ch = [cells[f"heuristic:{h}/{f}/churn:{ch}"]["sla_rate"]
                    for f in fleets]
            entry[f"heuristic_{h}_sla"] = _mean(h_ch)
            entry[f"heuristic_{h}_drop"] = _mean(
                [b - v for b, v in zip(h_base, h_ch)])
        entry["generalist_minus_specialist_sla"] = round(
            entry["generalist_sla"] - entry["specialist_diag_sla"], 4)
        robustness[ch] = entry
    summary = {
        "generalist_beats_untrained": all(gen[f] > unt[f] for f in fleets),
        "generalist_mean_sla": _mean(list(gen.values())),
        "untrained_mean_sla": _mean(list(unt.values())),
        "specialist_diag_mean_sla": _mean(diag),
        "specialist_offdiag_mean_sla": _mean(off) if off else None,
        "churn_robustness": robustness,
        "wall_s": round(time.time() - t_all, 1),
    }
    result = dict(
        meta=dict(**bench_meta(),
                  size=size_name, workload=workload, fleets=list(fleets),
                  m_max=m_max, desc_dim=spec.desc_dim, hidden=hidden,
                  episodes=episodes, periods=periods, seeds=n_seeds,
                  load=EVAL_LOAD, qos_factor=EVAL_QOS_FACTOR,
                  churns=list(churns), heuristics=list(HEURISTICS)),
        cells=cells, summary=summary)
    out = out or os.path.join(REPO, "BENCH_transfer.json")
    with open(out, "w") as fh:
        json.dump(result, fh, indent=1)
    print("transfer_summary," + json.dumps(summary), flush=True)
    print(f"transfer_json,{out}", flush=True)
    return result


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true",
                    help="paper-sized training budgets (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI grid (2 fleets by default)")
    ap.add_argument("--workload", default="light")
    ap.add_argument("--fleets", default=None,
                    help=f"comma list of fleet presets {fleet_names()}")
    ap.add_argument("--churn", default=None,
                    help="comma list of churn presets for the robustness "
                         f"section (default {','.join(DEFAULT_CHURNS)}; "
                         "smoke: fail)")
    ap.add_argument("--out", default=None, help="JSON output path")
    ap.add_argument("--verbose", action="store_true",
                    help="stream per-episode training logs")
    args = ap.parse_args(argv)
    fleets = (tuple(args.fleets.split(",")) if args.fleets
              else (("paper6", "8simba") if args.smoke else DEFAULT_FLEETS))
    run(quick=not args.full, smoke=args.smoke, workload=args.workload,
        fleets=fleets,
        churns=tuple(args.churn.split(",")) if args.churn
        else DEFAULT_CHURNS,
        out=args.out, verbose=args.verbose)


if __name__ == "__main__":
    main()
