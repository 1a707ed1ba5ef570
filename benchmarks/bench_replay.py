"""Chip microbenchmark: the counter replay of one leaf, Pallas kernel
against XLA's fusion.

Times ``ops.zo_replay_leaf`` with impl='pallas' (the ``zo_replay_flat``
kernel) and impl='auto' (XLA's fusion, ``kernels/ref.zo_replay_ref``)
on one parameter leaf, N records at a time, and prints
one JSON line per (N, impl): milliseconds per call, milliseconds per 10^9
counter normals drawn, the compile's temporary bytes, and the largest
difference between the two outputs. It needs a TPU and refuses to run
without one: a time from another backend says nothing about the chip.

    python3 benchmarks/bench_replay.py [--shape 22,2048,8192] \\
        [--n 1,8,32,2048]

The default leaf is internlm2-1.8b's stacked server-half MLP weight in
bfloat16. Lists of up to 64 records are timed over 5 calls after one
untimed call; a longer list is timed over its first call.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402


def timed(compiled, args, reps: int):
    """(seconds per call, last output) of an already compiled program;
    one untimed call first unless a single call is timed."""
    if reps > 1:
        jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shape", default="22,2048,8192")
    ap.add_argument("--n", default="1,8,32,2048")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench_replay: no TPU (found {dev.platform}); "
                         f"this benchmark never runs on another platform")
    shape = tuple(int(s) for s in args.shape.split(","))
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
    for n in (int(s) for s in args.n.split(",")):
        rng = np.random.default_rng(n)
        seeds = jnp.asarray(rng.integers(0, 2 ** 32, n, dtype=np.uint32))
        coeffs = jnp.asarray((rng.normal(size=n) * 1e-3).astype(np.float32))
        outs = {}
        for impl in ("pallas", "auto"):
            fn = jax.jit(lambda x, s, c, impl=impl: ops.zo_replay_leaf(
                x, s, c, impl=impl, interpret=False))
            compiled = fn.lower(x, seeds, coeffs).compile()
            sec, out = timed(compiled, (x, seeds, coeffs),
                             5 if n <= 64 else 1)
            outs[impl] = out.astype(jnp.float32)
            print(json.dumps({
                "device": dev.device_kind, "shape": list(shape), "n": n,
                "impl": impl, "ms": sec * 1e3,
                "ms_per_1e9_draws": sec * 1e3 / (n * x.size / 1e9),
                "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            }), flush=True)
        print(json.dumps({"n": n, "max_abs_diff": float(
            jnp.max(jnp.abs(outs["pallas"] - outs["auto"])))}), flush=True)


if __name__ == "__main__":
    main()
