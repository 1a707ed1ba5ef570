"""Bring-up check: MU-SplitFed training end to end on TPU.

    python3 chip_smoke.py               # one chip: phases a-e
    python3 chip_smoke.py --four-chips  # four chips: phase a, then only the
                                        # sharded fleet run and its reference

One process, no child processes. Every phase is fatal on failure:

  a. platform: the first device must be a TPU; there is no CPU fallback;
  b. kernel: the compiled seed-replay Pallas kernel against its jnp
     reference on a bf16 olmo-1b MLP leaf, at 64 and 2048 records;
  c. main path: ``repro.launch.train`` at olmo-1b's published width, four
     rounds in two chunks, every round's loss finite;
  d. counter-noise seed replay through ``engine.run_rounds`` at the same
     size, with the Pallas kernel in the compiled chunk program;
  e. the sparse semi-async path (DES, prefetch, donated ring store) at
     smoke width through ``repro.launch.train``.

``--four-chips`` runs the sparse path three ways on a four-device host:
fleet gather, O(K) subset staging (must match bit for bit) and the ring
store sharded over all four devices (must match within 5e-4, the gate of
tests/test_system.py). The last line printed is one JSON object naming the
device. Per-round logs go to ``chip_smoke_logs/``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import re
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
LOGS = ROOT / "chip_smoke_logs"

# the size of phases c and d: a cross-silo job of M=4 clients, olmo-1b at
# its published width, four rounds in two chunks
SIZE = {"clients": 4, "batch": 1, "seq": 1024, "tau": 2, "rounds": 4,
        "chunk-size": 2}
# dense aggregation keeps an f32 copy of the server half and does not fit
# one v5e at this size; seed replay applies the records instead
MAIN_PATH = (["--arch", "olmo-1b", "--client-mode", "sequential",
              "--aggregation", "seed_replay"]
             + [a for k, v in SIZE.items() for a in (f"--{k}", str(v))])
ASYNC_SMOKE = ["--arch", "olmo-1b", "--smoke", "--async", "--timeline",
               "sparse", "--quorum", "4", "--clients", "64", "--loader",
               "subset", "--batch", "1", "--seq", "64", "--rounds", "8",
               "--chunk-size", "4"]
# tests/test_system.py::test_train_driver_sharded_run_matches_unsharded
FLEET = ["--arch", "olmo-1b", "--smoke", "--rounds", "4", "--tau", "1",
         "--clients", "8", "--batch", "1", "--seq", "16", "--async",
         "--quorum", "3", "--staleness-discount", "0.5", "--timeline",
         "sparse", "--k-max", "8", "--ring-capacity", "16", "--chunk-size",
         "2", "--straggler-scale", "0.4"]
SHARDED_TOL = 5e-4


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def platform_check(want: int) -> dict:
    dev = jax.devices()
    d0 = dev[0]
    if d0.platform != "tpu":
        fail(f"no TPU found: the first device is {d0.platform!r} "
             f"({d0.device_kind}); this check never runs on the CPU")
    print(f"[a] platform {d0.platform}, device_kind {d0.device_kind!r}, "
          f"{len(dev)} device(s)")
    if len(dev) < want:
        fail(f"needs {want} devices, found {len(dev)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(dev)}


def max_abs_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def losses(log: Path) -> list:
    from repro.obs import read_jsonl
    return [r["loss"] for r in read_jsonl(str(log), "round")]


def measured_chunks(log: Path) -> list:
    """(start, stop, seconds) per chunk: the engine's block_until_ready-
    bracketed dispatch time, compilation included in the first."""
    from repro.obs import read_jsonl
    return [(t["start"], t["stop"], t["dispatch_seconds"])
            for row in read_jsonl(str(log), "chunk")
            for t in row["telemetry"] if t["source"] == "measured"]


def check_finite(name: str, vals) -> None:
    if not vals or not all(math.isfinite(v) for v in vals):
        fail(f"{name}: non-finite or missing losses {vals}")
    print(f"{name}: losses " + " ".join(f"{v:.4f}" for v in vals))


def phase_kernel() -> None:
    """b. compiled zo_replay_flat vs the jnp oracle on one bf16 leaf."""
    from repro.kernels import ops, ref
    shape = (2048, 8192)                       # olmo-1b MLP up-projection
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0.0, 0.02, shape), jnp.bfloat16)
    kernel = jax.jit(lambda x, s, c: ops.zo_replay_leaf(
        x, s, c, impl="pallas", interpret=False))
    oracle = jax.jit(ref.zo_replay_ref)
    for n in (64, ops.REPLAY_SMEM_RECORDS):
        seeds = jnp.asarray(rng.integers(0, 2 ** 32, n, dtype=np.uint32))
        coeffs = jnp.asarray(rng.normal(0.0, 1e-3, n), jnp.float32)
        got = kernel(x, seeds, coeffs)
        want = oracle(x, seeds, coeffs)
        diff = max_abs_diff(got, want)
        # Both accumulate the records in the same f32 order, so they differ
        # only in the last bits of Mosaic's and XLA's log/cos/sqrt. Such a
        # difference can move the final bf16 rounding by one step, and by
        # no more: the tolerance is one bf16 ulp at the largest |output|.
        top = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        tol = 2.0 ** (math.floor(math.log2(top)) - 7)
        n_diff = int(jnp.sum(got != want))
        print(f"[b] zo_replay {shape} bf16, {n} records: max |kernel - "
              f"reference| {diff:.3e} (tolerance {tol:.3e}), "
              f"{n_diff} of {x.size} elements differ")
        if not diff <= tol:
            fail(f"kernel differs from the reference by {diff} > {tol}")


def phase_main_path() -> None:
    """c. repro.launch.train at full width."""
    from repro.launch import train
    log = LOGS / "main_path.jsonl"
    params = train.main(MAIN_PATH + ["--log-jsonl", str(log)])
    n_params = sum(x.size for x in jax.tree.leaves(params))
    del params
    print(f"[c] olmo-1b: {n_params} parameters ({n_params / 1e9:.3f} B)")
    chunks = measured_chunks(log)
    if len(chunks) != 2:
        fail(f"expected two measured chunks, got {chunks}")
    (a0, a1, t_first), (b0, b1, t_second) = chunks
    print(f"[c] chunk [{a0}, {a1}) {t_first:.3f} s (compilation included); "
          f"chunk [{b0}, {b1}) {t_second:.3f} s")
    check_finite("[c] main path", losses(log))


def phase_counter_replay() -> None:
    """d. counter noise + seed replay through engine.run_rounds, at the
    size of phase c; the chunk program must hold the Pallas kernel."""
    import repro.obs as obs
    from repro.configs import SFLConfig, get_config
    from repro.core import engine
    from repro.core import straggler as strag
    from repro.data import FederatedLoader, SyntheticLM, dirichlet_partition
    from repro.models import init_params, untie_params
    cfg = get_config("olmo-1b")
    M, b, seq = SIZE["clients"], SIZE["batch"], SIZE["seq"]
    rounds, chunk = SIZE["rounds"], SIZE["chunk-size"]
    sfl = SFLConfig(n_clients=M, tau=SIZE["tau"],
                    cut_units=cfg.default_cut_units, lr_server=1e-3,
                    lr_client=5e-4, perturbation_dist="counter")
    algo = engine.get_algorithm("mu_splitfed", client_mode="sequential",
                                aggregation="seed_replay")
    key = jax.random.PRNGKey(0)
    params = untie_params(cfg, init_params(cfg, key))
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, seed=0)
    parts = dirichlet_partition(np.arange(4096) % 10, M, alpha=0.5, seed=0)
    loader = FederatedLoader(ds, parts, b, seed=0)
    sched = strag.make_schedule(
        0, rounds, population=strag.ClientPopulation.resolve(sfl))
    sink = obs.TelemetrySink()
    res = engine.run_rounds(algo, cfg, sfl, params, loader.round_batch,
                            sched, key, rounds=rounds, chunk_size=chunk,
                            telemetry=sink)
    for t in sink.records("measured"):
        print(f"[d] chunk [{t.start}, {t.stop}) {t.dispatch_seconds:.3f} s")
    check_finite("[d] counter seed replay", [float(v) for v in res.round_loss])

    # the chunk program the engine ran, compiled again on arguments of the
    # same shapes and (unset) placement: is the kernel in it?
    batch = loader.round_batch(0)
    args = (jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         res.params), (),
            {k: jax.ShapeDtypeStruct((chunk,) + v.shape, v.dtype)
             for k, v in batch.items()},
            jax.ShapeDtypeStruct((chunk, M), jnp.float32),
            jax.ShapeDtypeStruct((chunk, 2), jnp.uint32))
    t0 = time.perf_counter()
    lowered = jax.jit(engine.make_chunk_fn(algo, cfg, sfl),
                      donate_argnums=(0, 1)).lower(*args)
    t1 = time.perf_counter()
    hlo = lowered.compile().as_text()
    n_kernel = len(re.findall(r'custom_call_target="tpu_custom_call"', hlo))
    print(f"[d] compiled chunk program: {n_kernel} tpu_custom_call ops "
          f"(lowering {t1 - t0:.1f} s, compile or cache load "
          f"{time.perf_counter() - t1:.1f} s)")
    if not n_kernel:
        fail("the compiled chunk program holds no Pallas kernel")


def phase_async() -> None:
    """e. the sparse semi-async path at smoke width."""
    from repro.launch import train
    log = LOGS / "async.jsonl"
    train.main(ASYNC_SMOKE + ["--log-jsonl", str(log)])
    check_finite("[e] sparse async", losses(log))


def phase_four_chips() -> None:
    """Sharded ring store on four chips vs the replicated run."""
    from repro.launch import train
    ref = train.main(FLEET)
    sub = train.main(FLEET + ["--loader", "subset"])
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        shd = train.main(FLEET + ["--loader", "subset", "--fleet-shard", "4"])
    print(out.getvalue(), end="")
    m = re.search(r"final ring store on (\d+) devices (.*)", out.getvalue())
    if m is None or int(m.group(1)) != 4:
        fail(f"the ring store does not span 4 devices: "
             f"{m.group(0) if m else 'no placement line'}")
    ds, dh = max_abs_diff(ref, sub), max_abs_diff(ref, shd)
    print(f"[4] ring store spans {m.group(1)} devices; subset vs gather "
          f"max diff {ds}; sharded vs replicated max diff {dh:.3e} "
          f"(tolerance {SHARDED_TOL})")
    if ds != 0.0:
        fail(f"subset staging differs from the fleet gather: {ds}")
    if not dh <= SHARDED_TOL:
        fail(f"sharded run diverges from the replicated run: {dh}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet phase on 4 devices")
    args = ap.parse_args(argv)
    device = platform_check(4 if args.four_chips else 1)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    shutil.rmtree(LOGS, ignore_errors=True)
    LOGS.mkdir()
    if args.four_chips:
        phase_four_chips()
    else:
        for phase in (phase_kernel, phase_main_path, phase_counter_replay,
                      phase_async):
            t = time.perf_counter()
            phase()
            peak = (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0)
            print(f"{phase.__name__}: {time.perf_counter() - t:.1f} s, "
                  f"device peak so far {peak / 2 ** 30:.2f} GiB", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
