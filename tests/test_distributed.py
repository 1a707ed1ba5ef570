"""Multi-device correctness (subprocess: tests must not pollute this
process's device count). Verifies that a sharded MU-SplitFed round on an
8-device mesh produces the same numbers as the single-device run, and that
the dry-run machinery lowers/compiles on small meshes."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import SFLConfig, get_config
from repro.core.splitfed import mu_splitfed_round
from repro.models import init_params, untie_params
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_cell, lower_cell
from repro.configs.base import ShapeConfig

cfg = get_config("olmo-1b", smoke=True).replace(dtype="float32")
key = jax.random.PRNGKey(0)
params = untie_params(cfg, init_params(cfg, key))
M = 4
batches = {"tokens": jax.random.randint(key, (M, 2, 16), 0, cfg.vocab_size)}
batches["labels"] = batches["tokens"]
mask = jnp.ones((M,), jnp.float32)
sfl = SFLConfig(n_clients=M, tau=2, cut_units=1)

# single-device reference
p_ref, _ = mu_splitfed_round(cfg, sfl, params, batches, mask, key)

# sharded: M over data, TP over model
mesh = make_mesh((4, 2), ("data", "model"))
bsh = jax.tree.map(lambda a: jax.device_put(a, NamedSharding(mesh, P("data"))),
                   batches)
p_sh, _ = jax.jit(lambda p, b, m, k: mu_splitfed_round(cfg, sfl, p, b, m, k)
                  )(params, bsh, mask, key)
diff = max(float(jnp.max(jnp.abs(a - b)))
           for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
assert diff < 2e-5, f"sharded round diverges: {diff}"

# dry-run machinery on a small mesh (train + decode cells)
for shape in (ShapeConfig("t", 32, 8, "train"), ShapeConfig("d", 64, 8, "decode")):
    cell = build_cell("olmo-1b", shape, mesh, smoke=True,
                      sfl=sfl if shape.kind == "train" else None)
    lower_cell(cell).compile()

# fused multi-round cell (perf ladder v5): 2 rounds in one scan dispatch
from repro.launch.steps import build_train_multi_cell
mcell = build_train_multi_cell("olmo-1b", ShapeConfig("t", 32, 8, "train"),
                               mesh, smoke=True, sfl=sfl, rounds_per_chunk=2)
lower_cell(mcell).compile()
print("DISTRIBUTED_OK", diff)
"""


@pytest.mark.slow
def test_sharded_round_matches_single_device():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=560, cwd=REPO)
    assert "DISTRIBUTED_OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
