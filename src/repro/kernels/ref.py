"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
for the interpret-mode sweeps in tests/test_kernels.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# counter-based RNG (murmur3-finalizer hash -> Box-Muller gaussian)
# shared formula between ref and kernel: u[i] = gauss(seed, i)
# ---------------------------------------------------------------------------

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLD = np.uint32(0x9E3779B9)


def _hash_u32(seed, idx):
    """Murmur3 finalizer over (seed + idx*golden). uint32 arrays."""
    x = (idx * _GOLD + seed).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = (x * _M1).astype(jnp.uint32)
    x = x ^ (x >> 13)
    x = (x * _M2).astype(jnp.uint32)
    x = x ^ (x >> 16)
    return x


def _top24(h):
    """The top 24 bits of uint32 ``h`` as exact f32 values in [0, 2^24)."""
    return (h >> 8).astype(jnp.int32).astype(jnp.float32)


def counter_gauss(seed: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Standard normal from two independent hashes via Box-Muller (f32)."""
    seed = jnp.asarray(seed, jnp.uint32)
    idx = jnp.asarray(idx, jnp.uint32)
    h1 = _hash_u32(seed, idx)
    h2 = _hash_u32(seed ^ np.uint32(0xA5A5A5A5), idx)
    # uniforms from the top 24 bits, which f32 holds exactly; the cast goes
    # through int32 because Mosaic has no uint32 -> f32 conversion.
    # u1 in (0,1]: avoid log(0); u2 in [0,1)
    u1 = (_top24(h1) + 1.0) * (1.0 / 16777216.0)
    u2 = _top24(h2) * (1.0 / 16777216.0)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(2.0 * jnp.float32(jnp.pi) * u2)


def counter_gauss2(seed, hi, lo) -> jnp.ndarray:
    """2-D counter gaussian: (hi, lo) index pair — 2^64-element streams for
    >4B-parameter trees. hi/lo are uint32 arrays broadcast together."""
    seed = jnp.asarray(seed, jnp.uint32)
    mixed = (jnp.asarray(hi, jnp.uint32) * _M1 + seed).astype(jnp.uint32)
    return counter_gauss(mixed, jnp.asarray(lo, jnp.uint32))


LANE = 1024


def noise_rows(seed, row0: int, n_rows: int) -> jnp.ndarray:
    """(n_rows, LANE) standard-normal block; row r uses counter row0+r.
    The canonical noise layout shared by zo.tree_noise (dist='counter'),
    zo_update_ref, and the Pallas kernel."""
    hi = (jnp.arange(n_rows, dtype=jnp.uint32) + jnp.uint32(row0))[:, None]
    lo = jnp.arange(LANE, dtype=jnp.uint32)[None, :]
    return counter_gauss2(seed, jnp.broadcast_to(hi, (n_rows, LANE)),
                          jnp.broadcast_to(lo, (n_rows, LANE)))


# ---------------------------------------------------------------------------
# zo_update oracle: y = x + coeff * u over the (row, LANE) counter layout
# ---------------------------------------------------------------------------

def zo_update_ref(x: jnp.ndarray, seed, coeff, row_offset: int = 0
                  ) -> jnp.ndarray:
    n = x.size
    rows = -(-n // LANE)
    u = noise_rows(seed, row_offset, rows).reshape(-1)[:n].reshape(x.shape)
    return (x.astype(jnp.float32) + jnp.asarray(coeff, jnp.float32) * u
            ).astype(x.dtype)


# windowed-accumulation width: the Σ cᵢ·uᵢ accumulator is built as a
# lax.scan over windows of this many unrolled records. A flat N-record
# unroll fuses into one giant elementwise XLA fusion whose live noise
# temporaries scale with N (32.2 GB temp at N=32 on the fake 16×16 CPU
# mesh — perf_iterations.json v5 vs v5.1); the windowed scan bounds the
# fusion (and the temp footprint) at WINDOW records while still touching
# x exactly once. Accumulation order is identical to the sequential
# record order, so results are bit-identical to the old flat unroll.
_REPLAY_WINDOW = 8


def zo_replay_ref(x: jnp.ndarray, seeds, coeffs, row_offset: int = 0
                  ) -> jnp.ndarray:
    """Batched-replay oracle: y = x + Σᵢ coeffs[i]·u(seeds[i]).

    Matches zo_replay_flat (and N sequential zo_update_ref applications up
    to f32 summation order): the Σ cᵢ·uᵢ accumulator is built elementwise
    BEFORE x is touched, so the parameter leaf is read and written exactly
    once regardless of N. Above _REPLAY_WINDOW records the accumulation
    runs as a scan of WINDOW-record unrolled windows (records padded with
    zero coefficients to a whole window)."""
    seeds = jnp.asarray(seeds, jnp.uint32).reshape(-1)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(-1)
    n = seeds.shape[0]
    n_el = x.size
    rows = -(-n_el // LANE)
    hi = ((jnp.arange(rows, dtype=jnp.uint32) + jnp.uint32(row_offset))
          [:, None] + jnp.zeros((rows, LANE), jnp.uint32))
    lo = jnp.broadcast_to(jnp.arange(LANE, dtype=jnp.uint32)[None, :],
                          (rows, LANE))
    W = _REPLAY_WINDOW
    if n <= W:
        acc = jnp.zeros((rows, LANE), jnp.float32)
        for i in range(n):
            acc = acc + coeffs[i] * counter_gauss2(seeds[i], hi, lo)
    else:
        pad = (-n) % W                 # zero-coeff records contribute +0
        gs = jnp.pad(seeds, (0, pad)).reshape(-1, W)
        gc = jnp.pad(coeffs, (0, pad)).reshape(-1, W)

        def body(acc, sc):
            s, c = sc
            for j in range(W):
                acc = acc + c[j] * counter_gauss2(s[j], hi, lo)
            return acc, None

        acc, _ = jax.lax.scan(body, jnp.zeros((rows, LANE), jnp.float32),
                              (gs, gc))
    acc = acc.reshape(-1)[:n_el].reshape(x.shape)
    return (x.astype(jnp.float32) + acc).astype(x.dtype)


# ---------------------------------------------------------------------------
# rmsnorm oracle
# ---------------------------------------------------------------------------

def rmsnorm_ref(x: jnp.ndarray, scale: jnp.ndarray,
                eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# flash attention oracle (causal / sliding-window, GQA)
# ---------------------------------------------------------------------------

def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int = 0) -> jnp.ndarray:
    """q: (B, H, S, d); k, v: (B, Hkv, S, d). Returns (B, H, S, d)."""
    B, H, S, d = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, S, d)
    scores = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    ok = jnp.ones((S, S), bool)
    if causal:
        ok &= j <= i
    if window > 0:
        ok &= (i - j) < window
    scores = jnp.where(ok, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", p, v.astype(jnp.float32))
    return out.reshape(B, H, S, d).astype(q.dtype)
