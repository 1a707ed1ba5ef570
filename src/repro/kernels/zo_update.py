"""Fused SPSA perturb/update/replay Pallas TPU kernels.

The ZO training hot loop sweeps every parameter 2τ+3 times per round with
``x ± λu`` / ``x ← x − a·u``. A naive implementation reads x AND a
materialized u from HBM (two reads + one write). These kernels regenerate u
*inside VMEM* from a counter-based hash (murmur3 finalizer + Box-Muller —
the formula of ref.counter_gauss), making the op one HBM read + one
write (1.5× traffic reduction) and eliminating parameter-sized noise
storage entirely — the TPU realization of MeZO-style seed replay adapted to
the HBM→VMEM hierarchy.

Two entry points:
  zo_update_flat   y = x + c·u(seed)            (single record)
  zo_replay_flat   y = x + Σᵢ cᵢ·u(seedᵢ)       (batched seed replay)

``zo_replay_flat`` is the aggregation hot path (perf-ladder v4): replaying
the N = M·τ·P records of a seed-replay round as a lax.scan of single-record
updates costs N full HBM read+write sweeps of the parameters; the batched
kernel holds each (rows, LANE) block in VMEM, accumulates all N
counter-gaussian contributions there ((seeds, coeffs) live in SMEM), and
touches HBM once per block regardless of N — O(1) parameter sweeps instead
of O(Mτ P).

Layout: the caller flattens a leaf to (R, LANE) rows of 1024 lanes; the
grid walks row blocks; each block derives its global element indices from
program_id, so the noise stream is independent of blocking/sharding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the oracle's counter layout and noise formula, traced in-kernel
from repro.kernels.ref import LANE, counter_gauss2

BLOCK_ROWS = 256     # rows per grid step: 256 × 1024 × 4 B = 1 MiB VMEM


def _zo_update_kernel(seed_ref, coeff_ref, x_ref, o_ref, *, offset: int):
    i = pl.program_id(0)
    rows, lane = x_ref.shape
    row0 = jnp.uint32(offset) + jnp.uint32(i) * jnp.uint32(rows)
    hi = row0 + jax.lax.broadcasted_iota(jnp.uint32, (rows, lane), 0)
    lo = jax.lax.broadcasted_iota(jnp.uint32, (rows, lane), 1)
    u = counter_gauss2(seed_ref[0], hi, lo)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (x + coeff_ref[0] * u).astype(o_ref.dtype)


def zo_update_flat(x_flat: jnp.ndarray, seed: jnp.ndarray,
                   coeff: jnp.ndarray, *, offset: int = 0,
                   interpret: bool = False) -> jnp.ndarray:
    """y = x + coeff · u(seed) over a flat (R, LANE) f32/bf16 array.
    ``offset`` is the ROW offset into the (row, lane) counter space."""
    R, lane = x_flat.shape
    assert lane == LANE, f"lane dim must be {LANE}"
    rows = min(BLOCK_ROWS, R)
    assert R % rows == 0
    grid = (R // rows,)
    return pl.pallas_call(
        functools.partial(_zo_update_kernel, offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x_flat.shape, x_flat.dtype),
        interpret=interpret,
    )(jnp.asarray(seed, jnp.uint32).reshape(1),
      jnp.asarray(coeff, jnp.float32).reshape(1), x_flat)


def _zo_replay_kernel(seeds_ref, coeffs_ref, x_ref, o_ref, *, offset: int,
                      n: int):
    i = pl.program_id(0)
    rows, lane = x_ref.shape
    row0 = jnp.uint32(offset) + jnp.uint32(i) * jnp.uint32(rows)
    hi = row0 + jax.lax.broadcasted_iota(jnp.uint32, (rows, lane), 0)
    lo = jax.lax.broadcasted_iota(jnp.uint32, (rows, lane), 1)

    def body(j, acc):
        return acc + coeffs_ref[j] * counter_gauss2(seeds_ref[j], hi, lo)

    acc = jax.lax.fori_loop(0, n, body, jnp.zeros((rows, lane), jnp.float32))
    o_ref[...] = (x_ref[...].astype(jnp.float32) + acc).astype(o_ref.dtype)


def zo_replay_flat(x_flat: jnp.ndarray, seeds: jnp.ndarray,
                   coeffs: jnp.ndarray, *, offset: int = 0,
                   interpret: bool = False) -> jnp.ndarray:
    """y = x + Σᵢ coeffs[i]·u(seeds[i]) over a flat (R, LANE) f32/bf16 array.

    The batched form of ``zo_update_flat``: the N counter-gaussian noise
    contributions are regenerated and summed in VMEM, so the whole replay is
    one HBM read + one write per block regardless of N. seeds/coeffs are
    (N,) SMEM-resident scalars; ``offset`` is the ROW offset into the
    (row, lane) counter space (same stream as zo_update_flat)."""
    R, lane = x_flat.shape
    assert lane == LANE, f"lane dim must be {LANE}"
    seeds = jnp.asarray(seeds, jnp.uint32).reshape(-1)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(-1)
    n = seeds.shape[0]
    assert coeffs.shape[0] == n, (coeffs.shape, n)
    rows = min(BLOCK_ROWS, R)
    assert R % rows == 0
    grid = (R // rows,)
    return pl.pallas_call(
        functools.partial(_zo_replay_kernel, offset=offset, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((n,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x_flat.shape, x_flat.dtype),
        interpret=interpret,
    )(seeds, coeffs, x_flat)
