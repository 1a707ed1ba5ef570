"""jit'd public wrappers for the Pallas kernels. The counter replay of a
leaf (``zo_replay_leaf``) takes XLA's fusion unless the kernel is asked for.

``interpret`` defaults to True off-TPU so the same call sites run everywhere
(CPU containers execute the kernel bodies in interpret mode; TPU compiles
them). Pytree-level helpers flatten/pad leaves into the kernels' (R, LANE)
layout and give each leaf a disjoint slice of the counter space, so the
noise stream is identical regardless of leaf boundaries or sharding.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.zo_update import (BLOCK_ROWS, LANE, zo_replay_flat,
                                     zo_update_flat)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _auto_interpret(interpret):
    return (not on_tpu()) if interpret is None else interpret


# ---------------------------------------------------------------------------
# leaf + pytree ZO update
# ---------------------------------------------------------------------------

def zo_update_leaf(x: jnp.ndarray, seed, coeff, *, row_offset: int = 0,
                   interpret=None) -> jnp.ndarray:
    """y = x + coeff·u(seed) for an arbitrary-shaped leaf (pads to LANE).
    ``row_offset`` positions the leaf in the (row, lane) counter space."""
    interpret = _auto_interpret(interpret)
    n = x.size
    rows = -(-n // LANE)
    flat = jnp.pad(x.reshape(-1), (0, rows * LANE - n)).reshape(rows, LANE)
    out = zo_update_flat(flat, seed, coeff, offset=row_offset,
                         interpret=interpret)
    return out.reshape(-1)[:n].reshape(x.shape)


# per-leaf seed decorrelation — MUST stay in sync with zo._LEAF_SALT so a
# record written by the engine (zo.tree_noise dist='counter') replays here
# on the identical stream
_LEAF_SALT = 0x9E3779B9


def zo_update_tree(params: Any, seed, coeff, *, interpret=None) -> Any:
    """Fused seed-replay update over a whole pytree. Leaf i draws from its
    own salted seed (seed ^ i·φ) at row offset 0 — the exact stream of
    zo.tree_noise(dist='counter'), so ``zo_update_tree(p,
    zo.record_seeds(key), -c)`` equals ``zo.apply_update(p, key, c,
    'counter')``."""
    leaves, treedef = jax.tree.flatten(params)
    out = []
    for i, leaf in enumerate(leaves):
        leaf_seed = (jnp.asarray(seed, jnp.uint32)
                     ^ jnp.uint32((i * _LEAF_SALT) & 0xFFFFFFFF))
        out.append(zo_update_leaf(leaf, leaf_seed, coeff,
                                  interpret=interpret))
    return jax.tree.unflatten(treedef, out)


def zo_perturb_tree(params: Any, seed, eps, *, interpret=None) -> Any:
    """x + eps·u — the perturbation side of SPSA (same noise stream)."""
    return zo_update_tree(params, seed, eps, interpret=interpret)


# ---------------------------------------------------------------------------
# batched seed replay (perf-ladder v4 hot path)
# ---------------------------------------------------------------------------

# zo_replay_flat keeps (seeds, coeffs) in SMEM: 8 B per record. SMEM is
# tens of KiB per core, so the record list is bounded — past this many
# records the ops layer splits the list and sweeps the leaf once per
# chunk (ceil(N/bound) sweeps) instead of failing at lowering.
REPLAY_SMEM_RECORDS = 2048            # 2048 × 8 B = 16 KiB of SMEM


def zo_replay_leaf(x: jnp.ndarray, seeds, coeffs, *, impl: str = "auto",
                   interpret=None, max_records: int = 0) -> jnp.ndarray:
    """y = x + Σᵢ coeffs[i]·u(seeds[i]) for an arbitrary-shaped leaf, with
    one read and one write of x regardless of N.

    impl='auto' sums the records in float32 in one XLA fusion
    (``kernels/ref.zo_replay_ref``), in the leaf's own layout, windowed
    past 8 records; on a v5e it draws counter noise 1.3 to 1.7 times as
    fast as the Pallas kernel at every N from 1 to 2,048
    (``benchmarks/bench_replay.py``). impl='pallas' runs the
    ``zo_replay_flat`` kernel over the leaf padded to whole
    (BLOCK_ROWS, LANE) blocks; its (seeds, coeffs) list lives in SMEM, so
    a list longer than ``REPLAY_SMEM_RECORDS`` (``max_records`` overrides
    it in tests) is chunked here, one kernel sweep per chunk."""
    if impl == "auto":
        return _ref.zo_replay_ref(x, seeds, coeffs)
    assert impl == "pallas", impl
    interpret = _auto_interpret(interpret)
    seeds = jnp.asarray(seeds, jnp.uint32).reshape(-1)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(-1)
    bound = max_records or REPLAY_SMEM_RECORDS
    n = x.size
    rows = -(-n // LANE)
    # pad the row count to a whole number of grid blocks (the extra rows
    # draw unused counter noise and are sliced off below)
    block = min(BLOCK_ROWS, rows)
    rows = -(-rows // block) * block
    flat = jnp.pad(x.reshape(-1), (0, rows * LANE - n)).reshape(rows, LANE)
    for i in range(0, seeds.shape[0], bound):
        flat = zo_replay_flat(flat, seeds[i:i + bound], coeffs[i:i + bound],
                              interpret=interpret)
    return flat.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# rmsnorm / flash attention
# ---------------------------------------------------------------------------

def rmsnorm_op(x, scale, *, eps: float = 1e-5, interpret=None):
    return rmsnorm(x, scale, eps=eps, interpret=_auto_interpret(interpret))


def flash_attention_op(q, k, v, *, causal: bool = True, window: int = 0,
                       interpret=None, **kw):
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=_auto_interpret(interpret), **kw)
