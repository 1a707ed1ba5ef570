"""Zeroth-order (SPSA) engine with seed-replay.

The paper's estimator (Eq. 3): g(x) = (f(x+λu) − f(x−λu)) / (2λ) · u, with u
either Gaussian (MeZO-style) or uniform on the sphere √d·S^{d-1} (the
paper's choice). Perturbations are *never materialized as state*: each is a
pure function of a PRNG key, so

  * perturb-forward-perturb needs no extra parameter-sized buffer beyond the
    functional temporary (MeZO's trick, expressed functionally);
  * an entire ZO update is the scalar pair ``(key, coeff)`` — replaying it
    regenerates u on the fly. This is the "dimension-free communication" of
    paper Appendix A, and our compressed-aggregation wire format.

Replaying a BATCH of N records has two implementations:

  * ``replay_updates``        sequential lax.scan — N full parameter-sized
                              HBM read+write sweeps (ladder v3; the only
                              option for threefry gaussian/sphere noise);
  * ``fused_replay_updates``  one-pass batched replay for dist='counter'
                              (ladder v4): per leaf, all N counter-gaussian
                              contributions are regenerated and summed in
                              float32 by one XLA fusion
                              (``kernels/ops.zo_replay_leaf``) before x is
                              touched — one HBM read + one write per leaf
                              regardless of N. This is what makes
                              seed-replay aggregation O(1) parameter sweeps
                              instead of O(Mτ P).

An SPSA step applies its own P records through ``fused_replay_updates``
too. The update needs both perturbed losses, so on a full-size v5e program
XLA gives it a fusion of its own that draws the counter noise again rather
than holding a float32 copy of the perturbations' draw.

The counter noise stream is layout-unified with the kernels: element with
row-major linear index n in leaf i draws from
``counter_gauss2(base ^ i·φ, n // 1024, n % 1024)`` — identical for
tree_noise, the Pallas kernels, and the ref oracles, so a record written
by the engine replays through the kernels on bit-identical noise (summed
results agree up to f32 accumulation order).

All helpers are pytree-generic: they work on client halves, server halves,
or full models.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Params = Any


class UpdateRecord(NamedTuple):
    """One replayable ZO update: x <- x - coeff * u(key).  O(1) bytes."""
    key: jax.Array     # PRNG key
    coeff: jax.Array   # scalar f32 (already includes lr * delta / (2 lambda))


# ---------------------------------------------------------------------------
# noise
# ---------------------------------------------------------------------------

def _leaf_keys(key, params: Params):
    """One fold_in-derived key per leaf — deterministic in tree structure,
    independent of sharding/mesh (jax.random is shape-deterministic)."""
    leaves, treedef = jax.tree.flatten(params)
    keys = [jax.random.fold_in(key, i) for i in range(len(leaves))]
    return jax.tree.unflatten(treedef, keys)


_LEAF_SALT = 0x9E3779B9          # golden-ratio leaf decorrelation constant


def record_seeds(keys) -> jax.Array:
    """uint32 counter seed(s) from PRNG key(s): first ^ last key word.
    Accepts one key (shape (2,)) or a batch ((N, 2) / any leading dims);
    the scalar form is the per-record ``base`` of tree_noise('counter')."""
    raw = jnp.asarray(keys, jnp.uint32)
    return (raw[..., 0] ^ raw[..., -1]).astype(jnp.uint32)


def _leaf_seed(base, leaf_idx: int):
    """Per-leaf counter seed — shared by tree_noise('counter') and
    fused_replay_updates so both draw the identical stream."""
    return base ^ jnp.uint32((leaf_idx * _LEAF_SALT) & 0xFFFFFFFF)


def tree_noise(key, params: Params, dist: str = "gaussian") -> Params:
    """u with the same structure/shapes as params (f32 leaves).

    dist='gaussian': iid N(0,1) via threefry (jax.random).
    dist='counter' : iid N(0,1) via the counter-based murmur3+Box-Muller
        generator of kernels/ref.py — ~3 HLO ops/element instead of
        threefry's long chain; the Pallas zo_update / zo_replay kernels
        draw the same stream (beyond-paper optimization; still an exact
        SPSA gaussian).
    dist='sphere'  : gaussian scaled to ‖u‖=√d globally (the paper's
        √d·S^{d-1}); needs a global norm, hence two passes.
    """
    if dist == "counter":
        # elementwise in each leaf's own layout, so GSPMD partitions the
        # draw exactly like the parameter it perturbs; the counters split
        # the row-major index at the kernels' LANE, so the stream is the
        # (row, lane) one of the Pallas kernels and the kernels/ref.py
        # oracles, and records replay through fused_replay_updates
        from repro.kernels.ref import counter_noise
        leaves, treedef = jax.tree.flatten(params)
        base = record_seeds(jnp.asarray(key).reshape(-1))
        return jax.tree.unflatten(
            treedef, [counter_noise(_leaf_seed(base, i), leaf.shape)
                      for i, leaf in enumerate(leaves)])
    ks = _leaf_keys(key, params)
    u = jax.tree.map(lambda p, k: jax.random.normal(k, p.shape, jnp.float32),
                     params, ks)
    if dist == "sphere":
        sq = sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(u))
        d = sum(x.size for x in jax.tree.leaves(u))
        u = jax.tree.map(lambda x: x * (jnp.sqrt(float(d)) / jnp.sqrt(sq)), u)
    return u


def perturb(params: Params, key, scale, dist: str = "gaussian") -> Params:
    """x + scale * u(key). ``scale`` may be a traced scalar (e.g. ±λ)."""
    u = tree_noise(key, params, dist)
    return jax.tree.map(lambda p, n: (p + scale * n).astype(p.dtype), params, u)


def apply_update(params: Params, key, coeff, dist: str = "gaussian") -> Params:
    """x - coeff * u(key): replay one UpdateRecord."""
    return perturb(params, key, -coeff, dist)


def replay_updates(params: Params, keys, coeffs, dist: str = "gaussian") -> Params:
    """Apply a batch of records sequentially (order-independent: updates are
    additive once the coeffs are fixed). keys: (N,) key array; coeffs: (N,).

    Each scan step regenerates a full parameter-sized noise tree and does a
    full HBM read+write of params — N sweeps total. Prefer
    ``fused_replay_updates`` (one sweep) whenever dist='counter'."""
    def body(p, rec):
        k, c = rec
        return apply_update(p, k, c, dist), None
    out, _ = jax.lax.scan(body, params, (keys, coeffs))
    return out


def fused_replay_updates(params: Params, keys, coeffs,
                         dist: str = "gaussian",
                         impl: str = "auto") -> Params:
    """One-pass batched replay of N UpdateRecords: x − Σᵢ cᵢ·u(keyᵢ).

    The seed-replay aggregation hot path (perf-ladder v4). For
    dist='counter', each leaf's N counter-gaussian contributions are
    regenerated and summed in float32 by ``kernels/ops.zo_replay_leaf``
    (one XLA fusion, windowed past 8 records) before the leaf is touched:
    one HBM read + one write per leaf regardless of N, versus the N full
    parameter sweeps of the ``replay_updates`` scan. Equivalent to that
    scan up to f32 summation order (≤1e-5; see tests/test_replay.py).

    dist='gaussian'/'sphere' (threefry noise, not counter-replayable) fall
    back to the sequential scan. impl: 'auto' | 'fused' | 'scan' — 'scan'
    forces the sequential path (the v3 rung / equivalence baseline);
    'fused' asserts the one-pass path (counter only).
    """
    if impl == "scan" or (impl == "auto" and dist != "counter"):
        return replay_updates(params, keys, coeffs, dist)
    if dist != "counter":
        raise ValueError(
            f"fused replay requires dist='counter', got {dist!r}")
    from repro.kernels.ops import zo_replay_leaf
    seeds = record_seeds(keys)                       # (N,) uint32
    neg_coeffs = -jnp.asarray(coeffs, jnp.float32).reshape(-1)
    leaves, treedef = jax.tree.flatten(params)
    out = [zo_replay_leaf(leaf, _leaf_seed(seeds, i), neg_coeffs)
           for i, leaf in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)


def replay_weighted_records(params: Params, keys, coeffs, weights,
                            dist: str = "gaussian",
                            impl: str = "auto") -> Params:
    """Replay per-client record stacks with aggregation weights — the
    shared wire-format apply of every seed-replay aggregation site.

    keys: (M, ..., 2) stacked record keys; coeffs: (M, ...) matching
    scalars; weights: (M,) aggregation weights (e.g. η_g·w_m). Flattens to
    N = M·(...) records with coeff cᵢ·w_m and applies them through
    fused_replay_updates."""
    coeffs = jnp.asarray(coeffs, jnp.float32)
    w = jnp.asarray(weights, jnp.float32).reshape(
        (-1,) + (1,) * (coeffs.ndim - 1))
    flat_keys = keys.reshape((-1,) + keys.shape[-1:])
    return fused_replay_updates(params, flat_keys, (coeffs * w).reshape(-1),
                                dist, impl=impl)


# ---------------------------------------------------------------------------
# SPSA estimation
# ---------------------------------------------------------------------------

def spsa_delta(loss_of: Callable[[Params], jax.Array], params: Params, key,
               eps: float, dist: str = "gaussian") -> jax.Array:
    """δ = f(x+λu) − f(x−λu) for one perturbation. Two forwards."""
    lp = loss_of(perturb(params, key, +eps, dist))
    lm = loss_of(perturb(params, key, -eps, dist))
    return (lp - lm).astype(jnp.float32)


def spsa_step(loss_of: Callable[[Params], jax.Array], params: Params, key,
              eps: float, lr, n_perturbations: int = 1,
              dist: str = "gaussian") -> Tuple[Params, jax.Array, Tuple]:
    """One ZO-SGD step with P-perturbation averaging.

    Returns (new_params, mean_delta, records) where records = (keys, coeffs)
    are the replayable wire format (P entries). The step applies its
    records through ``fused_replay_updates``, as the aggregation replays
    them, so a replay lands on new_params bit for bit.
    """
    P = n_perturbations
    pkeys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(P))

    def one(i, carry):
        deltas = carry
        d = spsa_delta(loss_of, params, pkeys[i], eps, dist)
        return deltas.at[i].set(d)

    deltas = jax.lax.fori_loop(0, P, one, jnp.zeros((P,), jnp.float32))
    coeffs = lr * deltas / (2.0 * eps * P)
    new_params = fused_replay_updates(params, pkeys, coeffs, dist)
    return new_params, jnp.mean(deltas), (pkeys, coeffs)


def zo_gradient(loss_of: Callable[[Params], jax.Array], params: Params, key,
                eps: float, n_perturbations: int = 1,
                dist: str = "gaussian") -> Params:
    """Materialized ZO gradient estimate (tests / analysis only — training
    paths use spsa_step's replay form and never build this tree)."""
    P = n_perturbations
    g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    for i in range(P):
        k = jax.random.fold_in(key, i)
        d = spsa_delta(loss_of, params, k, eps, dist)
        u = tree_noise(k, params, dist)
        g = jax.tree.map(lambda a, n: a + (d / (2 * eps * P)) * n, g, u)
    return g
