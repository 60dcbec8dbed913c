import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import anywhere: jax locks
# the device count at first initialization.  Only the dry-run gets 512
# placeholder devices; tests/benchmarks see the real single device.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell,
print memory_analysis / cost_analysis, extract collective bytes, and
cache everything as JSON for the roofline report.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Failures (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system; --all records them per-cell and exits non-zero.
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import ARCH_NAMES, SHAPES, get_config, shape_applicable
from repro.launch import hlo_analysis
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, make_par
from repro.serve.engine import make_serve_prefill, make_serve_step
from repro.train.step import TrainConfig, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _mem_stats(compiled) -> Dict[str, float]:
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "host_temp_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    out["total_bytes_per_device"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: Optional[dict] = None,
             tag: str = "") -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "tag": tag, "status": "skipped", "reason": reason}
    if not ok:
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for s in mesh.shape.values():
        chips *= s
    par = make_par(mesh, multi_pod, cfg, shape, **(overrides or {}))
    tcfg = TrainConfig()
    (args, in_sh, out_sh) = input_specs(cfg, shape, par, tcfg)

    if shape.kind == "train":
        fn = make_train_step(cfg, par, tcfg)
        donate = (0,)
    elif shape.kind == "prefill":
        fn = make_serve_prefill(cfg, par, cache_len=shape.seq_len)
        donate = ()
    else:
        fn = make_serve_step(cfg, par)
        donate = (1,)

    # Analytic per-device residency of the step's inputs (weights, opt
    # state, caches, batch) from shard shapes — independent check on
    # memory_analysis, exact by construction.
    import numpy as np

    def _leaf_bytes(a, sh):
        shard = sh.shard_shape(a.shape)
        return int(np.prod(shard)) * a.dtype.itemsize

    args_bytes = sum(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(_leaf_bytes, args, in_sh)))

    t0 = time.time()
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = _mem_stats(compiled)
    xla_cost = compiled.cost_analysis() or {}
    xla_cost = {k: float(v) for k, v in xla_cost.items()
                if isinstance(v, (int, float)) and k in ("flops",
                                                         "bytes accessed")}
    # Loop-aware re-analysis: XLA's cost_analysis counts while bodies
    # once; scan-over-layers models need trip-count multipliers.
    hlo = compiled.as_text()
    costs = hlo_analysis.analyze_text(hlo)
    wire = sum(costs.wire.values())
    mf = rl.model_flops(cfg, shape)
    terms = rl.terms_from_cost(
        {"flops": costs.flops, "bytes accessed": costs.bytes}, wire, mf,
        chips, rl.chip_peaks(rl.V5E))

    rec.update({
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem,
        "input_bytes_per_device": args_bytes,
        "cost": {"flops": costs.flops, "bytes accessed": costs.bytes},
        "xla_cost_loop_body_once": xla_cost,
        "collectives": dict(costs.wire),
        "collective_counts": dict(costs.coll_counts),
        "bytes_by_op": {k: round(v) for k, v in sorted(
            costs.by_op.items(), key=lambda kv: -kv[1])[:12]},
        "terms": {
            "compute_s": terms.compute_s, "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "model_flops_global": mf,
            "useful_flops_ratio": terms.useful_flops_ratio,
            "roofline_fraction": terms.roofline_fraction,
        },
        "params": cfg.num_params(),
        "active_params": cfg.num_active_params(),
    })
    return rec


def cell_path(arch: str, shape: str, mesh_name: str, tag: str = "") -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape}__{mesh_name}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="recompute cached cells")
    ap.add_argument("--tag", default="", help="variant tag (perf iters)")
    ap.add_argument("--override", default="",
                    help="k=v[,k=v] ParallelConfig overrides")
    args = ap.parse_args()

    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = {"true": True, "false": False}.get(
            v.lower(), v if not v.isdigit() else int(v))

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = ([(a, s) for a in ARCH_NAMES for s in SHAPES]
             if args.all else [(args.arch, args.shape)])

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            path = cell_path(arch, shape, mesh_name, args.tag)
            if os.path.exists(path) and not args.force:
                print(f"[cache] {arch} {shape} {mesh_name}")
                continue
            print(f"[run]   {arch} {shape} {mesh_name} ...", flush=True)
            try:
                rec = run_cell(arch, shape, mp, overrides, args.tag)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "tag": args.tag, "status": "error",
                       "error": f"{type(e).__name__}: {e}"}
                failures += 1
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec["status"] == "ok":
                t = rec["terms"]
                print(f"  ok: compile={rec['compile_s']}s "
                      f"mem/dev={rec['memory'].get('total_bytes_per_device', 0)/2**30:.2f}GiB "
                      f"dominant={t['dominant']} "
                      f"roofline={t['roofline_fraction']:.3f}", flush=True)
            elif rec["status"] == "skipped":
                print(f"  skipped: {rec['reason']}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
