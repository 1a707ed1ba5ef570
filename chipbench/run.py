"""MU-SplitFed chip benchmark: one cell of BENCHMARK.json per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell names a model configuration (``chipbench/configs/<name>.json``) and
a traffic file (``chipbench/traffic/<name>.json``). The run

  1. refuses to start without a TPU, or with fewer chips than the cell asks
     for: it exits non-zero and prints no result;
  2. sets up: JAX's persistent compile cache at its fixed path, the
     weights made on the device from the seed in one jitted call, then two
     calls of ``engine.run_rounds`` (the program's training entry) with one
     adapter instance: the first compiles the chunk program and runs the
     first chunk, whose losses and parameter change are kept for the
     check; the second runs one warm chunk, whose time sizes the window;
  3. measures: one more ``run_rounds`` call over a whole number of chunks
     that lasts about ``--seconds`` (no compilation inside it; compiles
     are counted), with the profiler on under ``--trace 1``;
  4. checks: the plain reference (``reference.py``) follows the first
     chunk from the same weights and batches, and the numbers compared
     (``check.py``) are held to the cell's limits
     (``chipbench/limits/<cell>.json``);
  5. prints, as the last line of standard output, one JSON object: the
     end-to-end metrics (``--trace 0``) or the per-layer metrics
     (``--trace 1``), the device, and the numbers compared with their
     limits, which also end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""


def load_bench(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"chipbench: no {what} named {name!r} in BENCHMARK.json")


def device_info(chips: int) -> dict:
    """The device as JAX reports it; NoChip unless the first device is a
    TPU and there are at least ``chips`` of them. Never falls back."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU: the first device is "
                     f"{devs[0].platform!r} ({devs[0].device_kind}); this "
                     f"benchmark never runs on another platform")
    if len(devs) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    bench = load_bench()
    cell = find(bench["workloads"], args.workload, "workload")
    device = device_info(cell["chips"])
    from chipbench import cell as cell_mod
    out, lines = cell_mod.run(bench, cell, args.seed, args.seconds,
                              bool(args.trace), device, t_start=T_START)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
