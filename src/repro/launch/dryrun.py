import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes and record memory/cost/collective numbers.

The two lines above MUST stay the first statements in this module — jax
locks the device count at first init, and the dry-run needs 512 placeholder
host devices to build the 2×16×16 mesh. Do NOT export this flag anywhere
else (tests/benches see 1 device).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                    # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
        --shape train_4k --multi-pod --out dryrun.json
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import SHAPES, SHAPES_BY_NAME, get_config
from repro.configs.registry import ASSIGNED, cells
from repro.launch.hlo_analysis import analyze_compiled
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell, lower_cell


def run_cell(arch, shape, mesh, mesh_name, *, tau=2, aggregation="dense",
             verbose=True):
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, tau=tau, aggregation=aggregation)
    lowered = lower_cell(cell)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = analyze_compiled(compiled)
    rec = {
        "arch": arch, "shape": shape.name, "mesh": mesh_name,
        "status": "ok",
        "plan": {"client_mode": cell.plan.client_mode,
                 "fsdp": cell.plan.fsdp,
                 "aggregation": cell.plan.aggregation},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                       + getattr(mem, "output_size_in_bytes", 0)
                       + getattr(mem, "temp_size_in_bytes", 0)),
        "collectives": coll,
    }
    if verbose:
        n_dev = mesh.devices.size
        print(f"  plan={rec['plan']}  lower={t_lower:.0f}s "
              f"compile={t_compile:.0f}s")
        print(f"  memory_analysis: args={rec['argument_size_bytes']/2**30:.2f}GiB "
              f"out={rec['output_size_bytes']/2**30:.2f}GiB "
              f"temp={rec['temp_size_bytes']/2**30:.2f}GiB "
              f"(whole-program; ÷{n_dev} devices = "
              f"{rec['peak_bytes']/n_dev/2**30:.3f}GiB/device)")
        print(f"  cost_analysis: flops={rec['flops']:.3e} "
              f"bytes={rec['bytes_accessed']:.3e}")
        print(f"  collectives: {coll['summary']}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--aggregation", default="dense",
                    choices=["dense", "seed_replay"])
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args(argv)

    meshes = []
    if args.single_pod or not args.multi_pod:
        meshes.append(("16x16", make_production_mesh(multi_pod=False)))
    if args.multi_pod or not args.single_pod:
        meshes.append(("2x16x16", make_production_mesh(multi_pod=True)))

    todo = []
    for arch, shape, status in cells(include_skips=True):
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape.name != args.shape:
            continue
        todo.append((arch, shape, status))

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))
    failures = 0
    for mesh_name, mesh in meshes:
        for arch, shape, status in todo:
            tag = f"{arch} × {shape.name} × {mesh_name}"
            if status.startswith("skip"):
                print(f"[skip] {tag}: {status}")
                results.append({"arch": arch, "shape": shape.name,
                                "mesh": mesh_name, "status": status})
                continue
            print(f"[dry-run] {tag}")
            try:
                results.append(run_cell(arch, shape, mesh, mesh_name,
                                        tau=args.tau,
                                        aggregation=args.aggregation))
            except Exception as e:
                failures += 1
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape.name,
                                "mesh": mesh_name, "status": f"FAIL: {e}"})
            json.dump(results, open(args.out, "w"), indent=1)
    ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"\n== dry-run: {ok} ok, {failures} failed, "
          f"{sum(1 for r in results if str(r.get('status')).startswith('skip'))} skipped "
          f"-> {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
