import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract roofline terms — no allocation, ever.

The lines above MUST precede any jax-touching import: jax locks the
platform and device count at first backend init, and the dry-run needs
512 host placeholder devices (on a TPU host it would otherwise see the
local chips) to build the (2, 16, 16) production mesh. Smoke
tests and benchmarks never import this module, so they see 1 device.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all            # sweep
  python -m repro.launch.dryrun ... --multi-pod --include-soi

Per cell this emits a JSON record (results/dryrun/<arch>_<shape>_<mesh>
.json) with memory_analysis (proves HBM fit), cost_analysis (FLOPs /
bytes), the per-collective byte breakdown parsed from optimized HLO,
and the three roofline terms (launch/roofline.py). ``--all`` runs each
cell in a subprocess so one cell's failure (or compile-time RAM) cannot
poison the sweep.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, get_config
from repro.configs.base import SHAPES
from repro.core.kfac import KFACConfig
from repro.launch import roofline as rl
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "results", "dryrun")


def _mem_fields(compiled):
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             include_soi: bool, out_dir: str,
             kcfg: KFACConfig = KFACConfig()) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
              "programs": {}, "status": "ok"}

    skip = steps_mod.cell_skip_reason(cfg, shape)
    if skip:
        record["status"] = "skipped"
        record["reason"] = skip
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"{arch}_{shape_name}_{mesh_tag}.json"),
                "w") as f:
            json.dump(record, f, indent=1)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cells = steps_mod.build_cell(cfg, shape, mesh, kcfg,
                                 include_soi=include_soi)
    # set_mesh (not the bare Mesh context): makes the abstract mesh
    # visible to shard_hint inside traced model code.
    with jax.set_mesh(mesh):
        for cell in cells:
            t0 = time.monotonic()
            lowered = cell.lower()
            t_lower = time.monotonic() - t0
            t0 = time.monotonic()
            compiled = lowered.compile()
            t_compile = time.monotonic() - t0
            mem = _mem_fields(compiled)
            print(f"[{arch} x {shape_name} x {mesh_tag}] {cell.name}: "
                  f"memory_analysis={mem}", flush=True)
            roof = rl.analyze(lowered, compiled, chips)
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            print(f"[{arch} x {shape_name} x {mesh_tag}] {cell.name}: "
                  f"flops/dev={roof.flops_per_dev:.3e} "
                  f"bytes/dev={roof.bytes_per_dev:.3e} "
                  f"coll/dev={roof.coll_bytes_per_dev:.3e} "
                  f"bottleneck={roof.bottleneck}", flush=True)
            record["programs"][cell.name] = {
                "lower_s": t_lower,
                "compile_s": t_compile,
                "memory_analysis": mem,
                "roofline": roof.to_json(),
                "model_flops": rl.model_flops(cfg, shape),
            }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"{arch}_{shape_name}_{mesh_tag}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def sweep(archs, shapes, pods, include_soi, out_dir):
    """Run each cell in an isolated subprocess; summarize."""
    results = []
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in pods:
                mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
                path = os.path.join(
                    out_dir, f"{arch}_{shape_name}_{mesh_tag}.json")
                if os.path.exists(path):
                    with open(path) as f:
                        rec = json.load(f)
                    results.append(rec)
                    print(f"cached  {arch} {shape_name} {mesh_tag}: "
                          f"{rec['status']}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--out", out_dir]
                if multi_pod:
                    cmd.append("--multi-pod")
                if include_soi:
                    cmd.append("--include-soi")
                t0 = time.monotonic()
                proc = subprocess.run(
                    cmd, capture_output=True, text=True, timeout=7200)
                dt = time.monotonic() - t0
                if proc.returncode == 0 and os.path.exists(path):
                    with open(path) as f:
                        rec = json.load(f)
                    results.append(rec)
                    print(f"ok      {arch} {shape_name} {mesh_tag} "
                          f"({dt:.0f}s)")
                else:
                    tail = (proc.stderr or proc.stdout or "")[-2000:]
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_tag, "status": "failed",
                           "error": tail}
                    with open(path + ".failed", "w") as f:
                        json.dump(rec, f, indent=1)
                    results.append(rec)
                    print(f"FAILED  {arch} {shape_name} {mesh_tag} "
                          f"({dt:.0f}s)\n{tail}")
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "failed" for r in results)
    print(f"\nsweep: {n_ok} ok, {n_skip} skipped, {n_fail} failed "
          f"of {len(results)}")
    return 1 if n_fail else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="sweep both single- and multi-pod")
    ap.add_argument("--include-soi", action="store_true",
                    help="also lower stats_step/inv_step for train cells")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    if args.arch == "all" or args.shape == "all" or args.both_meshes:
        pods = [False, True] if (args.both_meshes or not args.multi_pod) \
            else [True]
        sys.exit(sweep(archs, shapes, pods, args.include_soi, args.out))

    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod,
                       args.include_soi, args.out)
        print(json.dumps(
            {k: v for k, v in rec.items() if k != "programs"}))
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
