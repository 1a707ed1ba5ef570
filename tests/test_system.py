"""End-to-end system behaviour: the training driver round-trips through
checkpoint restart (including SIGKILL mid-run), and the serve driver
generates coherent shapes."""
import json
import os
import signal
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _env(**overrides):
    """This process's environment (JAX_PLATFORMS included) with the repo's
    sources on PYTHONPATH, plus ``overrides``."""
    path = os.pathsep.join(p for p in (str(REPO / "src"),
                                       os.environ.get("PYTHONPATH")) if p)
    return {**os.environ, "PYTHONPATH": path, **overrides}


@pytest.mark.slow
def test_train_driver_checkpoint_restart():
    with tempfile.TemporaryDirectory() as d:
        base = [sys.executable, "-m", "repro.launch.train", "--arch",
                "olmo-1b", "--smoke", "--clients", "2", "--batch", "1",
                "--seq", "16", "--ckpt-dir", d, "--ckpt-every", "2"]
        env = _env()
        r1 = subprocess.run(base + ["--rounds", "3"], capture_output=True,
                            text=True, timeout=560, cwd=REPO, env=env)
        assert "round    2" in r1.stdout, r1.stdout + r1.stderr[-2000:]
        r2 = subprocess.run(base + ["--rounds", "5"], capture_output=True,
                            text=True, timeout=560, cwd=REPO, env=env)
        assert "[resume] from round" in r2.stdout, r2.stdout + r2.stderr[-2000:]
        assert "round    4" in r2.stdout


@pytest.mark.slow
def test_train_driver_sigkill_and_resume_bit_identical():
    """The host-kill fault (--faults kill=R) SIGKILLs the driver — no
    cleanup, no atexit, the real crash mode — right after the chunk
    containing round R flushes and BEFORE that chunk's checkpoint lands.
    A rerun without the kill flag must resume from the last good chunk
    boundary and produce a per-round loss log bit-identical to an
    uninterrupted run."""
    with tempfile.TemporaryDirectory() as d:
        env = _env()

        def cmd(tag, *extra):
            return [sys.executable, "-m", "repro.launch.train", "--arch",
                    "olmo-1b", "--smoke", "--clients", "2", "--batch", "1",
                    "--seq", "16", "--rounds", "8", "--chunk-size", "2",
                    "--ckpt-every", "2", "--ckpt-dir", f"{d}/{tag}_ckpt",
                    "--log-jsonl", f"{d}/{tag}.jsonl", *extra]

        ref = subprocess.run(cmd("ref"), capture_output=True, text=True,
                             timeout=560, cwd=REPO, env=env)
        assert "round    7" in ref.stdout, ref.stdout + ref.stderr[-2000:]

        killed = subprocess.run(cmd("kill", "--faults", "kill=5"),
                                capture_output=True, text=True, timeout=560,
                                cwd=REPO, env=env)
        assert killed.returncode == -signal.SIGKILL, \
            killed.stdout + killed.stderr[-2000:]
        assert "[faults] kill=5: SIGKILL after chunk [4, 6)" in killed.stdout
        # the killed chunk's rounds flushed but its checkpoint never
        # landed: the newest surviving step is the previous boundary
        steps = sorted(s for s in os.listdir(f"{d}/kill_ckpt")
                       if s.startswith("step_"))
        assert steps[-1] == "step_0000000003", steps

        resumed = subprocess.run(cmd("kill"), capture_output=True,
                                 text=True, timeout=560, cwd=REPO,
                                 env=env)
        assert "[resume] from round 4" in resumed.stdout, \
            resumed.stdout + resumed.stderr[-2000:]
        assert "round    7" in resumed.stdout

        def losses(path):
            with open(path) as fh:
                rows = [json.loads(line) for line in fh]
            return {r["round"]: r["loss"] for r in rows
                    if r.get("kind") == "round"}

        # RunLog truncated the killed run's replayed rows on resume, so
        # the stitched log must equal the uninterrupted one bit for bit
        ref_losses = losses(f"{d}/ref.jsonl")
        assert len(ref_losses) == 8
        assert losses(f"{d}/kill.jsonl") == ref_losses


def test_train_driver_validates_async_policy_flags():
    """Parse-time validation (no silent clamping inside the DES): quorum
    must fit the RESOLVED fleet, the discount must be a weight base in
    [0, 1], geometry overrides must be non-negative, and the sparse
    timeline only exists under --async."""
    from repro.launch import train
    base = ["--arch", "olmo-1b", "--smoke", "--rounds", "1", "--clients",
            "4", "--batch", "1", "--seq", "16"]
    with pytest.raises(SystemExit):        # quorum > n_clients
        train.main(base + ["--async", "--quorum", "9"])
    with pytest.raises(SystemExit):        # quorum > resolved population M
        train.main(base + ["--async", "--quorum", "5",
                           "--population", "tiered:2x1.0,2x0.5"])
    with pytest.raises(SystemExit):        # discount outside [0, 1]
        train.main(base + ["--async", "--quorum", "2",
                           "--staleness-discount", "1.5"])
    with pytest.raises(SystemExit):        # negative geometry override
        train.main(base + ["--async", "--quorum", "2", "--k-max", "-1"])
    with pytest.raises(SystemExit):        # sparse without --async
        train.main(base + ["--timeline", "sparse"])


def test_train_driver_validates_fleet_flags():
    """Parse-time validation of the fleet-scale knobs: --loader subset and
    --fleet-shard only exist on the sparse async path, shard counts must
    fit the device pool, and ring/k_max geometry must divide the 'data'
    axis — all rejected before any device work."""
    from repro.launch import train
    base = ["--arch", "olmo-1b", "--smoke", "--rounds", "1", "--clients",
            "4", "--batch", "1", "--seq", "16"]
    sparse = base + ["--async", "--quorum", "2", "--timeline", "sparse"]
    with pytest.raises(SystemExit):        # subset loader without sparse
        train.main(base + ["--loader", "subset"])
    with pytest.raises(SystemExit):        # subset under async but dense
        train.main(base + ["--async", "--quorum", "2", "--loader",
                           "subset"])
    with pytest.raises(SystemExit):        # fleet-shard without sparse
        train.main(base + ["--fleet-shard", "1"])
    with pytest.raises(SystemExit):        # negative shard count
        train.main(sparse + ["--fleet-shard", "-1"])
    with pytest.raises(SystemExit):        # more shards than devices
        train.main(sparse + ["--fleet-shard", "4097"])


def test_train_driver_rejects_indivisible_fleet_geometry():
    """An explicit ring/k_max geometry that does not divide the 'data'
    mesh axis is a launch-time SystemExit with the fix in the message,
    not a mid-run GSPMD surprise (subprocess: needs a multi-device
    host)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "olmo-1b",
         "--smoke", "--rounds", "1", "--clients", "6", "--batch", "1",
         "--seq", "16", "--async", "--quorum", "2", "--timeline",
         "sparse", "--k-max", "6", "--ring-capacity", "6",
         "--fleet-shard", "4"],
        capture_output=True, text=True, timeout=560, cwd=REPO,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode != 0
    assert "does not divide the 'data' axis" in r.stderr, r.stderr[-2000:]


@pytest.mark.slow
def test_train_driver_sharded_run_matches_unsharded():
    """The sharded-placement gate on a forced 4-device host mesh:
    --loader subset reproduces the fleet-gather run bit for bit (host
    staging never touches device math), and --fleet-shard 4 matches the
    replicated run within the sharded reduction-order budget
    (test_distributed allows 2e-5 per round; 4 training rounds here)."""
    script = (
        "import numpy as np, jax\n"
        "from repro.launch import train\n"
        "a = ['--arch','olmo-1b','--smoke','--rounds','4','--tau','1',\n"
        "     '--clients','8','--batch','1','--seq','16','--async',\n"
        "     '--quorum','3','--staleness-discount','0.5','--timeline',\n"
        "     'sparse','--k-max','8','--ring-capacity','16',\n"
        "     '--chunk-size','2','--straggler-scale','0.4']\n"
        "ref = train.main(a)\n"
        "sub = train.main(a + ['--loader','subset'])\n"
        "shd = train.main(a + ['--loader','subset','--fleet-shard','4'])\n"
        "def d(x, y):\n"
        "    return max(float(jax.numpy.max(jax.numpy.abs(u - v)))\n"
        "               for u, v in zip(jax.tree.leaves(x),\n"
        "                               jax.tree.leaves(y)))\n"
        "ds, dh = d(ref, sub), d(ref, shd)\n"
        "assert ds == 0.0, f'subset != fleet gather: {ds}'\n"
        "assert dh <= 5e-4, f'sharded diverges from unsharded: {dh}'\n"
        "print('SHARDED_OK', ds, dh)\n")
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=560, cwd=REPO,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert "SHARDED_OK" in r.stdout, r.stdout + r.stderr[-2000:]
