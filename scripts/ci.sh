#!/usr/bin/env bash
# Tier-1 verification (see ROADMAP.md). Run from the repo root:
#   scripts/ci.sh
# Extra pytest args pass through: scripts/ci.sh -k engine
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
CI_TMP="$(mktemp -d "${TMPDIR:-/tmp}/relmas_ci.XXXXXX")"
trap 'rm -rf "$CI_TMP"' EXIT
# pmap lint: the trainer is mesh-sharded (shard_map) and the migration
# window closed with the PR 6 pmap oracle's removal — no jax.pmap may
# appear under core, tagged or not.
if grep -rn "jax\.pmap" src/repro/core; then
  echo "ERROR: jax.pmap under src/repro/core — use the mesh" \
       "shard_map path (docs/ARCHITECTURE.md 'Mesh-sharded rounds')" >&2
  exit 1
fi
# print lint: all user-facing output flows through the telemetry plane
# (tele.note / tele.emit / console_line) so every line has a JSONL twin
# when --log-jsonl is on; the only sanctioned print() under src/repro
# is the console backend itself (docs/OBSERVABILITY.md).
if grep -rn "\bprint(" src/repro | grep -v "src/repro/telemetry/console.py"
then
  echo "ERROR: bare print() under src/repro — emit through" \
       "repro.telemetry (console_line / tele.note / tele.emit;" \
       "docs/OBSERVABILITY.md)" >&2
  exit 1
fi
python -m pytest -x -q "$@"
# README quickstart, run verbatim (keeps the docs honest): the ~60-line
# end-to-end example; SKIP_QUICKSTART=1 skips it.
if [ -z "${SKIP_QUICKSTART:-}" ]; then
  python examples/quickstart.py
fi
# smoke scenario sweep: exercises the scan-fused device-resident MAGMA
# path end-to-end (tiny population/generations, 2 scenarios, ~15s);
# SKIP_SWEEP=1 skips it.  Output goes to a temp dir, NOT the repo.
if [ -z "${SKIP_SWEEP:-}" ]; then
  python -m benchmarks.sweep --smoke --churn none \
    --out "$CI_TMP/BENCH_sweep_smoke.json"
  # two-fleet smoke: per-fleet re-characterization + recompile on the
  # homogeneous-dataflow extremes (fleet cells must both materialize)
  python -m benchmarks.sweep --smoke --fleets 8simba,8eyeriss \
    --scenarios default --policies fcfs,relmas --churn none \
    --out "$CI_TMP/BENCH_sweep_fleets_smoke.json"
  python - "$CI_TMP/BENCH_sweep_fleets_smoke.json" <<'PY'
import json, sys
cells = json.load(open(sys.argv[1]))["cells"]
for k in ("8simba/default/fcfs/bw16", "8eyeriss/default/fcfs/bw16"):
    assert k in cells, f"missing fleet cell {k}: {sorted(cells)}"
print(f"fleet sweep smoke: {len(cells)} cells OK")
PY
  # churn-sweep smoke: the churn axis end-to-end through the batched
  # evaluators — churned cells must materialize under their
  # /churn:<preset> keys NEXT TO the byte-stable no-churn keys, and the
  # per-policy robustness summary must cover the preset
  python -m benchmarks.sweep --smoke --fleets paper6 \
    --scenarios default,burst --policies fcfs,relmas --churn none,fail \
    --out "$CI_TMP/BENCH_sweep_churn_smoke.json"
  python - "$CI_TMP/BENCH_sweep_churn_smoke.json" <<'PY'
import json, sys
res = json.load(open(sys.argv[1]))
cells = res["cells"]
for sc in ("default", "burst"):
    for p in ("fcfs", "relmas"):
        for suf in ("", "/churn:fail"):
            k = f"paper6/{sc}/{p}/bw16{suf}"
            assert k in cells, f"missing churn cell {k}: {sorted(cells)}"
assert "fail" in res["summary"]["churn_sla_drop"], res["summary"]
print(f"churn sweep smoke: {len(cells)} cells OK")
PY
fi
# fused-trainer smoke: the README quickstart's 2-round training command
# (verbatim flags; outdir redirected into the CI tempdir) — device-side
# trace gen -> rollout -> donated ring write -> update scan -> sigma
# decay through the real driver; SKIP_TRAIN=1 skips
if [ -z "${SKIP_TRAIN:-}" ]; then
  python -m repro.launch.rl_train --workload light --episodes 4 \
    --batch-episodes 2 --periods 6 --max-rq 16 --max-jobs 8 --hidden 8 \
    --updates-per-episode 2 --batch-size 8 --replay-capacity 64 \
    --warmup-episodes 2 --eval-every 100 --eval-seeds 2 \
    --outdir "$CI_TMP/relmas_smoke"
  # sharded-trainer smoke: the same config mesh-sharded (shard_map)
  # over 2 forced host devices (--devices 2: split collection,
  # replicated update on the all_gathered global batch, per-device
  # double-buffered rings; see docs/ARCHITECTURE.md "Mesh-sharded
  # rounds")
  XLA_FLAGS="--xla_force_host_platform_device_count=2" \
  python -m repro.launch.rl_train --workload light --episodes 4 \
    --batch-episodes 2 --periods 6 --max-rq 16 --max-jobs 8 --hidden 8 \
    --updates-per-episode 2 --batch-size 8 --replay-capacity 64 \
    --warmup-episodes 2 --eval-every 100 --eval-seeds 2 --devices 2 \
    --outdir "$CI_TMP/relmas_sharded_smoke"
  # churn-trainer smoke: 2 fused rounds with a per-round drawn churn
  # schedule (SA failure mid-episode) through the real driver
  python -m repro.launch.rl_train --workload light --episodes 4 \
    --batch-episodes 2 --periods 6 --max-rq 16 --max-jobs 8 --hidden 8 \
    --updates-per-episode 2 --batch-size 8 --replay-capacity 64 \
    --warmup-episodes 2 --eval-every 100 --eval-seeds 2 --churn fail \
    --outdir "$CI_TMP/relmas_churn_smoke"
fi
# generalist smokes: (1) a 2-fleet --fleet training run (2 fused
# fleet-sampling rounds: descriptor-conditioned policy, stacked fleet
# tensors bound per round, M-agnostic replay) and (2) a 2x2 transfer
# matrix (trains 3 tiny policies in-suite) with a cell-presence check;
# SKIP_GENERALIST=1 skips both.  Outputs go to the CI tempdir.
if [ -z "${SKIP_GENERALIST:-}" ]; then
  python -m repro.launch.rl_train --workload light --fleet paper6,8simba \
    --episodes 4 --batch-episodes 2 --periods 6 --max-rq 16 --max-jobs 8 \
    --hidden 8 --updates-per-episode 2 --batch-size 8 \
    --replay-capacity 64 --warmup-episodes 2 --eval-every 100 \
    --eval-seeds 2 --outdir "$CI_TMP/generalist_smoke"
  python -m benchmarks.transfer --smoke \
    --out "$CI_TMP/BENCH_transfer_smoke.json"
  python - "$CI_TMP/BENCH_transfer_smoke.json" <<'PY'
import json, sys
res = json.load(open(sys.argv[1]))
cells = res["cells"]
for row in ("generalist", "specialist:paper6", "specialist:8simba",
            "untrained", "heuristic:fcfs", "heuristic:herald"):
    for f in ("paper6", "8simba"):
        assert f"{row}/{f}" in cells, \
            f"missing transfer cell {row}/{f}: {sorted(cells)}"
        assert f"{row}/{f}/churn:fail" in cells, \
            f"missing churned transfer cell {row}/{f}: {sorted(cells)}"
assert "generalist_beats_untrained" in res["summary"]
assert "fail" in res["summary"]["churn_robustness"], res["summary"]
print(f"transfer smoke: {len(cells)} cells OK")
PY
fi
# bench regression guard: fresh train_throughput must stay within 30%
# of the committed BENCH_rollout.json.  Absolute rounds/sec is machine-
# dependent, so a failure requires BOTH the absolute fused rounds/sec
# AND the machine-invariant fused/hostloop speedup (both arms measured
# in the same fresh run) to regress >30%.  The devices subsection is
# guarded the same way: its 2-device (shard_map) rounds/sec AND the
# machine-invariant 2dev/1dev scaling ratio must both regress >30% to
# fail (and the 1/2-device rows must be present).  The shardmap_1dev
# machinery arm's rounds/sec row is dual-condition guarded vs the
# committed file.  The devices section times every arm in this one
# process, so its 4 host devices are set on the command line; SKIP_BENCH=1
# skips
if [ -z "${SKIP_BENCH:-}" ]; then
  XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  python -m benchmarks.rollout_throughput --only train_throughput \
    --out "$CI_TMP/BENCH_rollout_fresh.json"
  python - "$CI_TMP/BENCH_rollout_fresh.json" <<'PY'
import json, sys
fresh = json.load(open(sys.argv[1]))["train_throughput"]
committed = json.load(open("BENCH_rollout.json"))["train_throughput"]
new, old = fresh["rounds_per_sec_fused"], committed["rounds_per_sec_fused"]
new_sp, old_sp = fresh["speedup"], committed["speedup"]
print(f"train_throughput guard: fused rounds/sec {new} vs committed {old}; "
      f"speedup {new_sp}x vs committed {old_sp}x")
if new < 0.7 * old and new_sp < 0.7 * old_sp:
    sys.exit(f"REGRESSION: fused trainer rounds/sec {new} < 70% of "
             f"committed {old} AND speedup {new_sp}x < 70% of "
             f"committed {old_sp}x")
fd, cd = fresh.get("devices", {}), committed.get("devices", {})
for row in ("1", "2"):
    assert row in fd.get("counts", {}), \
        f"devices scaling section missing {row}-device row: {fd}"
assert fd["counts"]["2"].get("impl") == "shard_map", \
    f"2-device row is not the shard_map arm: {fd['counts']['2']}"
assert "shardmap_1dev" in fd, \
    f"devices section missing machinery arm shardmap_1dev: {fd}"
ov_sm = fd["overhead_1dev_shardmap"]
print(f"devices machinery: overhead_1dev shard_map {ov_sm}")
if cd:
    new2 = fd["counts"]["2"]["rounds_per_sec"]
    old2 = cd["counts"]["2"]["rounds_per_sec"]
    new_sc, old_sc = fd["scaling_2dev"], cd["scaling_2dev"]
    print(f"devices guard: 2-dev rounds/sec {new2} vs committed {old2}; "
          f"scaling_2dev {new_sc} vs committed {old_sc}")
    if new2 < 0.7 * old2 and new_sc < 0.7 * old_sc:
        sys.exit(f"REGRESSION: sharded 2-device rounds/sec {new2} < 70% "
                 f"of committed {old2} AND scaling_2dev {new_sc} < 70% "
                 f"of committed {old_sc}")
    if "shardmap_1dev" in cd:
        new1 = fd["shardmap_1dev"]["rounds_per_sec"]
        old1 = cd["shardmap_1dev"]["rounds_per_sec"]
        old_ov = cd["overhead_1dev_shardmap"]
        print(f"shardmap_1dev guard: rounds/sec {new1} vs committed {old1};"
              f" overhead {ov_sm} vs committed {old_ov}")
        if new1 < 0.7 * old1 and ov_sm > old_ov / 0.7:
            sys.exit(f"REGRESSION: shard_map 1-device rounds/sec {new1} < "
                     f"70% of committed {old1} AND overhead {ov_sm} > "
                     f"1/0.7x committed {old_ov}")
PY
fi
# serving bench: (1) loadgen smoke — one scenario at low rate through
# the batched single-dispatch tick (8 streams, ~1 min) with a
# cell-presence + bit-parity check; (2) regression guard at the
# committed config (96 streams): the acceptance conditions (batched
# tick >= 5x host-loop requests/sec AND bit-equal SLA on the same
# workloads) must hold fresh, and the requests/sec + p99-latency rows
# must stay within 30% of the committed BENCH_serving.json — absolute
# numbers are machine-dependent, so each row fails only when BOTH the
# absolute value AND its machine-invariant ratio (speedup /
# latency_ratio, both arms measured in the same fresh run) regress
# >30%.  SKIP_SERVING=1 skips both.
if [ -z "${SKIP_SERVING:-}" ]; then
  python -m benchmarks.serving_bench --smoke \
    --out "$CI_TMP/BENCH_serving_smoke.json"
  python - "$CI_TMP/BENCH_serving_smoke.json" <<'PY'
import json, sys
res = json.load(open(sys.argv[1]))
cells = res["scenarios"]["cells"]
assert "steady/0.5" in cells, f"missing loadgen cell: {sorted(cells)}"
assert cells["steady/0.5"]["counted"] > 0, cells["steady/0.5"]
assert res["guard"]["throughput"]["sla_equal"], \
    f"batched tick lost bit-parity: {res['guard']['throughput']}"
print(f"serving smoke: {len(cells)} loadgen cell(s), parity OK")
PY
  python -m benchmarks.serving_bench --only guard \
    --out "$CI_TMP/BENCH_serving_fresh.json"
  python - "$CI_TMP/BENCH_serving_fresh.json" <<'PY'
import json, sys
fresh = json.load(open(sys.argv[1]))["guard"]
committed = json.load(open("BENCH_serving.json"))["guard"]
ft, ct = fresh["throughput"], committed["throughput"]
fl, cl = fresh["decision_latency"], committed["decision_latency"]
assert ft["sla_equal"], \
    f"batched tick lost bit-parity with the host loop: {ft}"
assert ft["meets_5x"], \
    f"batched tick below 5x acceptance bar: {ft['speedup']}x " \
    f"({ft['rps_batched']} vs {ft['rps_host']} req/s)"
print(f"serving guard: rps {ft['rps_batched']} vs committed "
      f"{ct['rps_batched']}; speedup {ft['speedup']}x vs "
      f"{ct['speedup']}x; tick p99 {fl['tick_p99_us']}us vs "
      f"{cl['tick_p99_us']}us")
if ft["rps_batched"] < 0.7 * ct["rps_batched"] \
        and ft["speedup"] < 0.7 * ct["speedup"]:
    sys.exit(f"REGRESSION: batched requests/sec {ft['rps_batched']} < "
             f"70% of committed {ct['rps_batched']} AND speedup "
             f"{ft['speedup']}x < 70% of committed {ct['speedup']}x")
if fl["tick_p99_us"] > cl["tick_p99_us"] / 0.7 \
        and fl["latency_ratio"] > cl["latency_ratio"] / 0.7:
    sys.exit(f"REGRESSION: tick p99 {fl['tick_p99_us']}us > 1/0.7x "
             f"committed {cl['tick_p99_us']}us AND latency ratio "
             f"{fl['latency_ratio']} > 1/0.7x committed "
             f"{cl['latency_ratio']}")
PY
fi
# telemetry smoke: a 2-round trainer and a batched serving run, each
# streaming --log-jsonl, then scripts/metrics_summary.py validates
# every line against repro.telemetry.schema and requires the stream's
# load-bearing record kinds (see docs/OBSERVABILITY.md);
# SKIP_TELEMETRY=1 skips.
if [ -z "${SKIP_TELEMETRY:-}" ]; then
  python -m repro.launch.rl_train --workload light --episodes 4 \
    --batch-episodes 2 --periods 6 --max-rq 16 --max-jobs 8 --hidden 8 \
    --updates-per-episode 2 --batch-size 8 --replay-capacity 64 \
    --warmup-episodes 2 --eval-every 100 --eval-seeds 2 \
    --outdir "$CI_TMP/telemetry_smoke" \
    --log-jsonl "$CI_TMP/telemetry_train.jsonl"
  python scripts/metrics_summary.py "$CI_TMP/telemetry_train.jsonl" \
    --require run_header,train_round,train_eval,span,run_end
  python -m repro.launch.serve --workload light --policy fcfs --batched \
    --streams 4 --requests 8 --periods 8 --max-rq 16 --max-jobs 16 \
    --window 8 --log-jsonl "$CI_TMP/telemetry_serve.jsonl"
  python scripts/metrics_summary.py "$CI_TMP/telemetry_serve.jsonl" \
    --require run_header,serve_window,tenant,serve_summary,run_end
fi
