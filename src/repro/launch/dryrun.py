import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (the two lines above MUST precede any jax import: jax locks the device
#  count on first init.  Tests shrink the placeholder fleet via env.)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod AOT dry-run: prove the distribution config is coherent.

For every (architecture x input shape) cell this lowers + compiles the
cell's step — train_step / prefill / decode — against ShapeDtypeStruct
stand-ins (zero allocation) on the production mesh:

  single-pod  (16, 16)    = 256 chips   (data, model)     [roofline table]
  multi-pod   (2, 16, 16) = 512 chips   (pod, data, model)

and records ``memory_analysis()`` (fits-in-HBM evidence),
``cost_analysis()`` (FLOPs/bytes) and the collective schedule parsed
from the partitioned HLO (roofline §Roofline).  The RELMAS DDPG update
itself is lowered as the extra cell ``--arch relmas`` (the paper's
technique participates in the multi-pod dry-run).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape train_4k [--multi-pod] [--out runs/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import (ARCHS, batch_specs, cache_specs,
                                    get_arch, shapes_for)
from repro.launch import hlo_analysis as HA
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.telemetry.console import console_line
from repro.models import partition as PT
from repro.models import sharding as shd
from repro.models.model import build_model
from repro.models.steps import (make_decode_step, make_prefill_step,
                                make_train_step)

HBM_PER_CHIP = 16 * 1024 ** 3     # v5e


def _parse_overrides(pairs: list[str]) -> dict[str, tuple[str, ...]]:
    out = {}
    for p in pairs or []:
        k, v = p.split("=")
        out[k] = tuple(a for a in v.split("+") if a) if v else ()
    return out


def _n_params(params_s) -> tuple[int, int]:
    """(total, active) param counts; active discounts idle experts."""
    total = expert = active_expert = 0
    flat = jax.tree_util.tree_flatten_with_path(params_s)[0]
    for path, leaf in flat:
        ks = PT._keystr(path)
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if leaf.ndim >= 3 and any(t in ks for t in
                                  ("w_gate", "w_up", "w_down")):
            expert += n
    return total, expert


def _active_params(cfg, params_s) -> int:
    total, expert = _n_params(params_s)
    if cfg.is_moe and expert:
        frac = cfg.top_k / cfg.n_experts
        if cfg.family == "hybrid":      # MoE only on alternating sublayers
            pass
        return int(total - expert + expert * frac)
    return total


def _mem_stats(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
        out["per_chip_total_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
        out["fits_16GB_hbm"] = out["per_chip_total_bytes"] <= HBM_PER_CHIP
    except Exception as e:                                 # pragma: no cover
        out["error"] = repr(e)
    return out


def _cost(compiled) -> dict:
    c = compiled.cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0]
    return {k: float(v) for k, v in c.items()
            if k in ("flops", "bytes accessed", "transcendentals")}


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------
def lower_cell(arch: str, shape_name: str, mesh, *, smoke: bool = False,
               overrides: dict | None = None, grad_accum: int | None = None):
    """Returns (lowered, aux) for one (arch, shape, mesh) cell."""
    if arch == "relmas":
        return _lower_relmas(shape_name, mesh)
    cfg = get_arch(arch, smoke=smoke)
    import dataclasses
    if grad_accum is not None:
        cfg = dataclasses.replace(cfg, grad_accum=grad_accum)
    if os.environ.get("REPRO_UNROLL"):      # §Perf: unrolled production
        cfg = dataclasses.replace(cfg, scan_unroll=True)
    return lower_cfg_cell(cfg, shape_name, mesh, overrides=overrides)


def lower_cfg_cell(cfg, shape_name: str, mesh, *, overrides: dict | None
                   = None):
    """Lower one step for an explicit ArchConfig (roofline cost modules
    pass unrolled/reduced-layer variants here)."""
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    multi_pod = "pod" in mesh.axis_names
    rules = shd.make_rules(multi_pod, overrides=overrides)
    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = PT.param_shardings(params_s, mesh, rules)
    b_s = batch_specs(cfg, shape)
    b_sh = PT.batch_shardings(b_s, mesh, rules)
    repl = NamedSharding(mesh, P())
    aux = {"params_s": params_s, "cfg": cfg}

    if shape.kind == "train":
        step, opt = make_train_step(model, mesh=mesh, rules=rules)
        opt_s = jax.eval_shape(opt.init, params_s)
        o_sh = PT.opt_shardings(opt_s, mesh, rules)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh, repl),
                     donate_argnums=(0, 1))
        lowered = fn.lower(params_s, opt_s, b_s,
                           jax.ShapeDtypeStruct((), jnp.int32))
    elif shape.kind == "prefill":
        step = make_prefill_step(model, mesh=mesh, rules=rules)
        fn = jax.jit(step, in_shardings=(p_sh, b_sh))
        lowered = fn.lower(params_s, b_s)
    else:   # decode
        step = make_decode_step(model, mesh=mesh, rules=rules)
        cache_s = cache_specs(cfg, shape)
        c_sh = PT.cache_shardings(cache_s, mesh, rules)
        fn = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh),
                     donate_argnums=(1,))
        lowered = fn.lower(params_s, cache_s, b_s)
    return lowered, aux


def _lower_relmas(shape_name: str, mesh):
    return _lower_relmas_T(mesh, T=97), _RELMAS_AUX


_RELMAS_AUX = {"params_s": None, "cfg": None}


def _lower_relmas_T(mesh, *, T: int = 97, B: int = 4096):
    """The paper's own DDPG update on the production mesh: replay batch
    sharded over (pod?, data); tiny policy replicated (DESIGN.md §3).
    T = LSTM sequence length (96 RQ slots + primer in production).
    REPRO_RL_DTYPE=bfloat16 selects the §Perf-H3 compute dtype."""
    from repro.core import ddpg as D
    from repro.core import policy as Pol
    M = 6                                     # paper MAS: 6 SAs
    pcfg = Pol.PolicyConfig(
        feat_dim=4 + 2 * M, act_dim=1 + M, hidden=256,
        compute_dtype=os.environ.get("REPRO_RL_DTYPE", "float32"))
    dcfg = D.DDPGConfig(policy=pcfg)
    state_s = jax.eval_shape(lambda k: D.init_ddpg(k, dcfg),
                             jax.random.PRNGKey(0))
    b_s = dict(
        s=jax.ShapeDtypeStruct((B, T, pcfg.feat_dim), jnp.float32),
        mask=jax.ShapeDtypeStruct((B, T), jnp.bool_),
        a=jax.ShapeDtypeStruct((B, T - 1, pcfg.act_dim), jnp.float32),
        r=jax.ShapeDtypeStruct((B,), jnp.float32),
        s2=jax.ShapeDtypeStruct((B, T, pcfg.feat_dim), jnp.float32),
        mask2=jax.ShapeDtypeStruct((B, T), jnp.bool_),
    )
    multi_pod = "pod" in mesh.axis_names
    rules = shd.make_rules(multi_pod)
    b_sh = PT.batch_shardings(b_s, mesh, rules)
    repl = jax.tree.map(lambda _: NamedSharding(mesh, P()), state_s)
    fn = jax.jit(lambda st, b: D.ddpg_update(st, dcfg, b),
                 in_shardings=(repl, b_sh), donate_argnums=(0,))
    return fn.lower(state_s, b_s)


# ---------------------------------------------------------------------------
def _mesh_from_shape(spec: str):
    """'2x4' -> (data, model) mesh; '2x2x4' -> (pod, data, model)."""
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return make_mesh(dims, axes)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             smoke: bool = False, overrides: dict | None = None,
             grad_accum: int | None = None, verbose: bool = True,
             mesh_shape: str | None = None, roofline: bool = False) -> dict:
    mesh = (_mesh_from_shape(mesh_shape) if mesh_shape
            else make_production_mesh(multi_pod=multi_pod))
    n_dev = mesh.size
    rec = dict(arch=arch, shape=shape_name,
               mesh=f"{'x'.join(map(str, mesh.devices.shape))}",
               devices=n_dev, multi_pod=multi_pod,
               overrides={k: list(v) for k, v in (overrides or {}).items()})
    t0 = time.time()
    try:
        lowered, aux = lower_cell(arch, shape_name, mesh, smoke=smoke,
                                  overrides=overrides,
                                  grad_accum=grad_accum)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        rec["mem"] = _mem_stats(compiled)
        cost = _cost(compiled)
        rec["cost"] = cost
        coll = HA.collective_stats(compiled.as_text(), n_dev)
        # raw terms from the rolled module (while bodies counted once —
        # recorded for reference; §Roofline uses the unrolled cost modules)
        rec["roofline_raw"] = HA.roofline_terms(cost, coll, n_dev)
        if roofline and not smoke:
            from repro.launch.roofline import roofline_cell
            t2 = time.time()
            rec["roofline"] = roofline_cell(arch, shape_name, mesh,
                                            overrides=overrides)
            rec["roofline_s"] = round(time.time() - t2, 2)
        if aux.get("cfg") is not None:
            cfg = aux["cfg"]
            total, _ = _n_params(aux["params_s"])
            active = _active_params(cfg, aux["params_s"])
            rec["n_params"] = total
            rec["n_active"] = active
            mf = HA.model_flops(cfg, SHAPES[shape_name], total, active)
            rec["model_flops"] = mf
            flops_chip = rec.get("roofline", {}).get(
                "flops_per_chip", cost.get("flops", 0.0))
            hlo_total = flops_chip * n_dev
            rec["useful_flop_ratio"] = (mf / hlo_total) if hlo_total else 0.0
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    if verbose:
        dom = rec.get("roofline", rec.get("roofline_raw", {})).get(
            "dominant", "-")
        console_line(f"[dryrun] {arch:>16s} x {shape_name:<12s} "
                     f"mesh={rec['mesh']:>8s} "
                     f"ok={rec['ok']} dominant={dom} "
                     f"(lower {rec.get('lower_s', '-')}s, "
                     f"compile {rec.get('compile_s', '-')}s)")
        if rec["ok"]:
            console_line("  memory_analysis: " + json.dumps(rec["mem"]))
            console_line("  cost_analysis: " + json.dumps(rec["cost"]))
        else:
            console_line("  ERROR: " + str(rec["error"]))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="arch id or 'relmas' (default: all)")
    ap.add_argument("--shape", default=None, help="shape id (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="use reduced configs (CI)")
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--override", action="append", default=[],
                    help="logical=axis[+axis] sharding-rule override")
    ap.add_argument("--mesh-shape", default=None,
                    help="CI override, e.g. 2x4 (with REPRO_DRYRUN_DEVICES)")
    ap.add_argument("--roofline", action="store_true",
                    help="also compile unrolled cost modules for accurate "
                         "roofline terms (single-pod table)")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args(argv)

    overrides = _parse_overrides(args.override)
    cells: list[tuple[str, str]] = []
    archs = [args.arch] if args.arch else list(ARCHS) + ["relmas"]
    for a in archs:
        if a == "relmas":
            cells.append((a, "train_4k"))
            continue
        shp = ([args.shape] if args.shape
               else shapes_for(get_arch(a, smoke=args.smoke)))
        cells += [(a, s) for s in shp]
    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]

    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            rec = run_cell(arch, shape, multi_pod=mp, smoke=args.smoke,
                           overrides=overrides, grad_accum=args.grad_accum,
                           mesh_shape=args.mesh_shape,
                           roofline=args.roofline and not mp)
            n_fail += 0 if rec["ok"] else 1
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    console_line(f"[dryrun] done: {len(cells) * len(meshes)} cells, "
                 f"{n_fail} failures")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
