"""Readings that the check's limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--fault half_batch --fault-seeds 1,2,3] \\
        --out <file.json>

For each seed, in one process: the program's readings from the first call
of the timed path (no measured window) and the float32 reference's; for
each control seed, the float8 control's (the reference one precision step
down, in the program's place); for each fault seed, the program's readings
with the fault planted (faults.py). Every comparison is written to
``--out`` with the numbers ``check.numbers`` gives, and printed. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault", default="")
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from chipbench import cell as cell_mod, check, faults, traffic
    from chipbench.run import device_info, find, load_bench
    from repro.launch.compile_cache import enable_compile_cache
    bench = load_bench()
    cell = find(bench["workloads"], args.workload, "workload")
    device = device_info(cell["chips"])
    enable_compile_cache()
    doc = cell_mod.load_config(bench, cell["config"])
    t = traffic.load(cell["traffic"])
    rows, refs = [], {}

    def record(kind, seed, prog, ref, seconds):
        nums = check.numbers(prog, ref)
        row = {"kind": kind, "seed": seed, **nums, "seconds": seconds,
               "loss": prog["loss"], "ref_loss": ref["loss"],
               "norms": prog["norms"], "ref_norms": ref["norms"],
               "client_loss": prog["client_loss"],
               "ref_client_loss": ref["client_loss"]}
        rows.append(row)
        print(json.dumps({k: row[k] for k in ("kind", "seed", *nums,
                                              "seconds")}), flush=True)
        Path(args.out).write_text(json.dumps(
            {"workload": args.workload, "device": device, "rows": rows},
            indent=1))

    def reference(seed):
        if seed not in refs:
            refs[seed] = cell_mod.Job(doc, t, seed).reference("f32")
        return refs[seed]

    for s in args.seeds:
        t0 = time.perf_counter()
        job = cell_mod.Job(doc, t, s)
        prog = job.first_call()
        job.params = None
        record("program", s, prog, reference(s), time.perf_counter() - t0)
    for s in args.control_seeds:
        t0 = time.perf_counter()
        ctl = cell_mod.Job(doc, t, s).reference("fp8")
        record("control_fp8", s, ctl, reference(s), time.perf_counter() - t0)
    if args.fault:
        with faults.planted(args.fault):
            for s in args.fault_seeds:
                t0 = time.perf_counter()
                job = cell_mod.Job(doc, t, s)
                prog = job.first_call()
                job.params = None
                record(f"fault_{args.fault}", s, prog, reference(s),
                       time.perf_counter() - t0)


if __name__ == "__main__":
    main()
