import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init).  512 placeholder host devices let jax.make_mesh build
# the production meshes: 16x16 (one pod of 256 v5e chips) and 2x16x16 (2 pods).

# Multi-pod dry-run: ``.lower().compile()`` every (arch x shape x mesh) cell,
# print ``memory_analysis()`` (proves the cell fits 16 GB/chip HBM) and
# ``cost_analysis()`` (FLOPs/bytes for §Roofline), parse the collective
# schedule from the optimized HLO, and write one JSON artifact per cell.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--force] [--quick]
#   PYTHONPATH=src python -m repro.launch.dryrun --cell ARCH SHAPE MESH

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"
HBM_PER_CHIP = 16 * 1024**3  # v5e
MESHES = ("single", "multi")


def cell_path(arch: str, shape: str, mesh: str) -> Path:
    return ARTIFACTS / f"{arch}__{shape}__{mesh}.json"


def run_cell(arch: str, shape_name: str, mesh_name: str) -> dict:
    import jax

    from ..configs import SHAPES, get_config, shape_applicable
    from ..models.profiles import active_params, total_params
    from ..models.sharding import make_rules, mesh_rules
    from ..roofline.analysis import Roofline
    from ..roofline.hlo_cost import analyze_hlo
    from .mesh import make_production_mesh
    from .specs import input_specs

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_dev = mesh.size
    # fsdp (batch over every axis) only pays when the batch covers the mesh;
    # below that it duplicates non-weight compute on idle axes and bloats
    # small-batch cells (measured: qwen2 train multi 2.5 -> 25.9 GB).  §Perf.
    strategy = (cfg.sharding_strategy
                if shape.global_batch >= n_dev else "2d")
    rules = make_rules(mesh, strategy)
    t0 = time.perf_counter()
    spec = input_specs(cfg, shape, rules)
    with mesh_rules(rules):
        jitted = jax.jit(spec["fn"], out_shardings=spec["out_shardings"],
                         donate_argnums=spec["donate"])
        lowered = jitted.lower(*spec["args"])
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    print(f"[{arch}|{shape_name}|{mesh_name}] memory_analysis:", mem)
    cost = compiled.cost_analysis()
    builtin_flops = float(cost.get("flops", 0.0))
    builtin_bytes = float(cost.get("bytes accessed", 0.0))
    print(f"[{arch}|{shape_name}|{mesh_name}] cost_analysis (builtin, "
          f"while-bodies-once): flops={builtin_flops:.3e} bytes={builtin_bytes:.3e}")
    # Trip-count-aware analysis over the optimized HLO: XLA's HloCostAnalysis
    # counts while bodies once, undercounting a 48-layer scan 48x and hiding
    # the collectives inside it — see roofline/hlo_cost.py.
    hlo = compiled.as_text()
    mc = analyze_hlo(hlo, n_dev)
    flops = mc.flops
    hbm_bytes = mc.bytes
    coll = {"bytes_per_device": mc.coll_bytes, "counts": mc.coll_counts,
            "total_bytes_per_device": mc.total_coll_bytes,
            "unknown_trip_counts": mc.unknown_trip_counts}
    print(f"[{arch}|{shape_name}|{mesh_name}] trip-aware: flops={flops:.3e} "
          f"bytes={hbm_bytes:.3e} coll={mc.total_coll_bytes:.3e}")

    n_active = active_params(cfg)
    tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
    factor = 6.0 if shape.kind == "train" else 2.0
    model_flops = factor * n_active * tokens

    rf = Roofline(arch=arch, shape=shape_name, mesh=mesh_name, chips=n_dev,
                  flops_per_device=flops, hbm_bytes_per_device=hbm_bytes,
                  coll_bytes_per_device=coll["total_bytes_per_device"],
                  model_flops_global=model_flops)
    per_dev = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "status": "ok",
        "devices": n_dev,
        "t_lower_s": t_lower, "t_compile_s": t_compile,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_bytes": per_dev,
            "fits_16gb": bool(per_dev <= HBM_PER_CHIP),
        },
        "cost": {"flops_per_device": flops, "hbm_bytes_per_device": hbm_bytes,
                 "builtin_flops": builtin_flops, "builtin_bytes": builtin_bytes},
        "collectives": coll,
        "params": {"total": total_params(cfg), "active": n_active},
        "tokens": tokens,
        "roofline": rf.to_dict(),
        "hlo_bytes": len(hlo),
    }
    return result


def enumerate_cells(quick: bool = False):
    from ..configs import ARCHS, SHAPES

    archs = sorted(ARCHS)
    shapes = list(SHAPES)
    if quick:
        archs, shapes = archs[:2], ["train_4k"]
    for arch in archs:
        for shape in shapes:
            for mesh in MESHES:
                yield arch, shape, mesh


def run_all(force: bool = False, quick: bool = False,
            timeout_s: float = 2400.0) -> int:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    failures = 0
    cells = list(enumerate_cells(quick))
    for i, (arch, shape, mesh) in enumerate(cells):
        out = cell_path(arch, shape, mesh)
        if out.exists() and not force:
            prev = json.loads(out.read_text())
            print(f"[{i+1}/{len(cells)}] {arch} {shape} {mesh}: cached "
                  f"({prev.get('status')})")
            failures += prev.get("status") == "error"
            continue
        t0 = time.time()
        env = dict(os.environ)
        src = str(Path(__file__).resolve().parents[2])
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"  # fake host devices; never the chip
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--cell", arch,
             shape, mesh],
            capture_output=True, text=True, timeout=timeout_s, env=env)
        status = "ok" if proc.returncode == 0 else "error"
        if proc.returncode != 0:
            failures += 1
            out.write_text(json.dumps({
                "arch": arch, "shape": shape, "mesh": mesh, "status": "error",
                "stderr": proc.stderr[-4000:], "stdout": proc.stdout[-2000:],
            }, indent=2))
        info = json.loads(out.read_text())
        print(f"[{i+1}/{len(cells)}] {arch} {shape} {mesh}: "
              f"{info.get('status')} in {time.time()-t0:.0f}s "
              + (f"compile={info.get('t_compile_s', 0):.0f}s "
                 f"fits={info.get('memory', {}).get('fits_16gb')}"
                 if info.get("status") == "ok" else ""))
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", nargs=3, metavar=("ARCH", "SHAPE", "MESH"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--timeout", type=float, default=2400.0)
    args = ap.parse_args()
    if args.cell:
        arch, shape, mesh = args.cell
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        try:
            result = run_cell(arch, shape, mesh)
        except Exception:
            cell_path(arch, shape, mesh).write_text(json.dumps({
                "arch": arch, "shape": shape, "mesh": mesh, "status": "error",
                "stderr": traceback.format_exc()[-4000:]}, indent=2))
            raise
        cell_path(arch, shape, mesh).write_text(json.dumps(result, indent=2))
        print(json.dumps({k: v for k, v in result.items() if k != "hlo"},
                         indent=2, default=str))
    else:
        sys.exit(1 if run_all(args.force, args.quick, args.timeout) else 0)


if __name__ == "__main__":
    main()
