"""The ZO Pallas kernels compile for a described TPU v5e at olmo-1b widths.

Interpret mode accepts programs that the chip's compiler refuses (a
uint32 -> f32 cast once passed every interpret test and failed here), so
these cases compile the kernels for a v5e that is described, not attached,
and check that the Mosaic kernel is in the program. The topology is
described inside a fixture: only the worker that runs this file loads the
TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import REPLAY_SMEM_RECORDS
from repro.kernels.zo_update import (BLOCK_ROWS, LANE, zo_replay_flat,
                                     zo_update_flat)

# bf16 olmo-1b leaves: the stacked server-half MLP weight, the embedding,
# and a norm-sized vector (fewer than 8 rows of LANE)
LEAVES = {"mlp_stack": (14, 2048, 8192), "embed": (50304, 2048),
          "vector": (2048,)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU compiler otherwise writes its logs under /tmp
    set_log_dir = "TPU_LOG_DIR" not in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
    if set_log_dir:
        os.environ.pop("TPU_LOG_DIR")


def _flat_rows(shape) -> int:
    """Rows of the (R, LANE) layout zo_replay_leaf builds: padded to a
    whole number of BLOCK_ROWS blocks."""
    n = 1
    for d in shape:
        n *= d
    rows = -(-n // LANE)
    block = min(BLOCK_ROWS, rows)
    return -(-rows // block) * block


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n_records", [1, 64, REPLAY_SMEM_RECORDS])
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_zo_replay_flat_compiles_for_v5e(one_chip, leaf, n_records):
    rows = _flat_rows(LEAVES[leaf])
    compiled = _compile(zo_replay_flat, one_chip,
                        ((rows, LANE), jnp.bfloat16),
                        ((n_records,), jnp.uint32),
                        ((n_records,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_zo_update_flat_compiles_for_v5e(one_chip):
    rows = _flat_rows(LEAVES["mlp_stack"])
    compiled = _compile(zo_update_flat, one_chip,
                        ((rows, LANE), jnp.bfloat16),
                        ((), jnp.uint32), ((), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()
