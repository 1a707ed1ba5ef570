"""Fused batched seed-replay engine (perf-ladder v4): equivalence of the
one-pass replay against the sequential scan path at every level —
kernel (interpret mode), pytree engine, and full MU-SplitFed / GAS rounds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import lm_batch, maxdiff, tiny_lm_cfg
from repro.configs import SFLConfig
from repro.core import zo
from repro.core.baselines import gas_init_state, gas_round
from repro.core.splitfed import mu_splitfed_round
from repro.kernels import ref
from repro.kernels.ops import zo_replay_leaf
from repro.kernels.zo_update import LANE, zo_replay_flat, zo_update_flat
from repro.models import init_params, untie_params

NS = [1, 8, 64]


def _records(n, salt=0):
    rng = np.random.default_rng(1234 + salt)
    seeds = jnp.asarray(rng.integers(0, 2 ** 32, size=n, dtype=np.uint32))
    coeffs = jnp.asarray((rng.normal(size=n) * 0.1).astype(np.float32))
    return seeds, coeffs


# ---------------------------------------------------------------------------
# kernel level: zo_replay_flat == N × zo_update_flat == ref oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", NS)
def test_zo_replay_flat_equals_sequential_updates(n):
    """One batched kernel call must equal N single-record kernel calls
    (up to f32 summation order)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, LANE), jnp.float32)
    seeds, coeffs = _records(n)
    fused = zo_replay_flat(x, seeds, coeffs, interpret=True)
    seq = x
    for i in range(n):
        seq = zo_update_flat(seq, seeds[i], coeffs[i], interpret=True)
    assert float(jnp.max(jnp.abs(fused - seq))) <= 1e-5


@pytest.mark.parametrize("n", NS)
def test_zo_replay_flat_equals_ref(n):
    x = jax.random.normal(jax.random.PRNGKey(1), (4, LANE), jnp.float32)
    seeds, coeffs = _records(n, salt=1)
    fused = zo_replay_flat(x, seeds, coeffs, interpret=True)
    want = ref.zo_replay_ref(x, seeds, coeffs)
    assert float(jnp.max(jnp.abs(fused - want))) <= 1e-5


def test_zo_replay_leaf_pallas_equals_ref_padded():
    """Odd-shaped leaf exercises the pad/unpad path of both backends."""
    x = jax.random.normal(jax.random.PRNGKey(2), (37, 11), jnp.float32)
    seeds, coeffs = _records(8, salt=2)
    a = zo_replay_leaf(x, seeds, coeffs, impl="pallas", interpret=True)
    b = zo_replay_leaf(x, seeds, coeffs, impl="auto")
    assert a.shape == x.shape
    assert float(jnp.max(jnp.abs(a - b))) <= 1e-5


def test_zo_replay_ref_windowed_scan_matches_blockwise():
    """Above the window width the ref switches to a lax.scan of 8-record
    unrolled windows (bounded XLA temp footprint) — same stream, same
    sequential record order."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, LANE), jnp.float32)
    seeds, coeffs = _records(16 * ref._REPLAY_WINDOW + 3, salt=3)
    big = ref.zo_replay_ref(x, seeds, coeffs)
    acc = x
    for i in range(0, seeds.shape[0], 16):
        acc = ref.zo_replay_ref(acc, seeds[i:i + 16], coeffs[i:i + 16])
    assert float(jnp.max(jnp.abs(big - acc))) <= 1e-4


def test_zo_replay_ref_window_boundary_padding():
    """The windowed scan (n > W, zero-coeff padded to a whole window) must
    reproduce the sequential-order accumulation of the same records —
    padding contributes exactly zero, only compiler-level fma fusion may
    differ."""
    x = jax.random.normal(jax.random.PRNGKey(12), (2, LANE), jnp.float32)
    n = ref._REPLAY_WINDOW + 3            # ragged: exercises the padding
    seeds, coeffs = _records(n, salt=12)
    windowed = ref.zo_replay_ref(x, seeds, coeffs)
    acc = jnp.zeros_like(x)
    hi = jnp.zeros((2, LANE), jnp.uint32) + jnp.arange(2, dtype=jnp.uint32)[:, None]
    lo = jnp.broadcast_to(jnp.arange(LANE, dtype=jnp.uint32)[None, :], (2, LANE))
    for i in range(n):
        acc = acc + coeffs[i] * ref.counter_gauss2(seeds[i], hi, lo)
    assert float(jnp.max(jnp.abs(windowed - (x + acc)))) <= 1e-6


def test_zo_replay_leaf_chunks_past_smem_bound():
    """N past the kernel's SMEM record bound must be split at the ops
    layer into multiple fused sweeps, not fail at lowering — forced here
    with a tiny bound so 13 records take 4 kernel calls."""
    x = jax.random.normal(jax.random.PRNGKey(13), (37, 11), jnp.float32)
    seeds, coeffs = _records(13, salt=13)
    chunked = zo_replay_leaf(x, seeds, coeffs, impl="pallas",
                             interpret=True, max_records=4)
    want = zo_replay_leaf(x, seeds, coeffs, impl="auto")
    assert float(jnp.max(jnp.abs(chunked - want))) <= 1e-5


# ---------------------------------------------------------------------------
# engine level: fused_replay_updates == replay_updates (counter dist)
# ---------------------------------------------------------------------------

def _tree(key):
    ka, kb, kc = jax.random.split(key, 3)
    return {"a": jax.random.normal(ka, (33, 17), jnp.float32),
            "b": {"c": jax.random.normal(kb, (5,), jnp.float32),
                  "d": jax.random.normal(kc, (3, 4, 5), jnp.float32)}}


@pytest.mark.parametrize("n", NS)
def test_fused_replay_updates_matches_scan(n):
    params = _tree(jax.random.PRNGKey(4))
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(5), i)
                    )(jnp.arange(n))
    coeffs = jnp.asarray(
        (np.random.default_rng(n).normal(size=n) * 0.05).astype(np.float32))
    fused = zo.fused_replay_updates(params, keys, coeffs, dist="counter")
    scan = zo.replay_updates(params, keys, coeffs, dist="counter")
    assert maxdiff(fused, scan) <= 1e-5


def test_fused_replay_gaussian_falls_back_to_scan():
    """Threefry dists are not counter-replayable: auto must produce the
    scan result bit-for-bit."""
    params = _tree(jax.random.PRNGKey(6))
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(7), i)
                    )(jnp.arange(4))
    coeffs = jnp.full((4,), 0.01, jnp.float32)
    fused = zo.fused_replay_updates(params, keys, coeffs, dist="gaussian")
    scan = zo.replay_updates(params, keys, coeffs, dist="gaussian")
    assert maxdiff(fused, scan) == 0.0


def test_fused_impl_requires_counter():
    params = {"w": jnp.zeros((8,))}
    keys = jax.random.PRNGKey(0)[None]
    with pytest.raises(ValueError):
        zo.fused_replay_updates(params, keys, jnp.ones((1,)),
                                dist="gaussian", impl="fused")


def test_zo_update_tree_matches_engine_stream():
    """ops.zo_update_tree now draws the engine's per-leaf salted stream:
    replaying an engine record through it must be bit-identical to
    zo.apply_update(dist='counter')."""
    from repro.kernels.ops import zo_update_tree
    params = _tree(jax.random.PRNGKey(10))
    key = jax.random.PRNGKey(11)
    engine = zo.apply_update(params, key, 0.25, dist="counter")
    kernel = zo_update_tree(params, zo.record_seeds(key), -0.25)
    assert maxdiff(engine, kernel) == 0.0


def test_spsa_step_records_replay_through_fused_path():
    """spsa_step's returned records replayed via the fused path must land on
    the exact same params spsa_step itself produced (both go through
    fused_replay_updates with dist='counter')."""
    loss = lambda p: sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(p))
    params = _tree(jax.random.PRNGKey(8))
    new_p, _, (keys, coeffs) = zo.spsa_step(loss, params,
                                            jax.random.PRNGKey(9),
                                            1e-3, 0.1, 3, dist="counter")
    replayed = zo.fused_replay_updates(params, keys, coeffs, dist="counter")
    assert maxdiff(new_p, replayed) == 0.0


def _rows_replay(params, keys, coeffs):
    """x − Σ_j c_j·u_j per leaf with the noise drawn in the (rows, LANE)
    layout of ``ref.noise_rows``, summed in f32 and rounded once: what the
    Pallas kernel and its oracle compute, independent of tree_noise."""
    seeds = zo.record_seeds(keys)
    leaves, treedef = jax.tree.flatten(params)
    out = []
    for i, x in enumerate(leaves):
        rows = -(-x.size // LANE)
        acc = jnp.zeros(x.shape, jnp.float32)
        for j in range(seeds.shape[0]):
            u = ref.noise_rows(zo._leaf_seed(seeds[j], i), 0, rows)
            acc = acc + -coeffs[j] * u.reshape(-1)[:x.size].reshape(x.shape)
        out.append((x.astype(jnp.float32) + acc).astype(x.dtype))
    return jax.tree.unflatten(treedef, out)


def _quad(p):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree.leaves(p))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_counter_spsa_step_applies_its_record_as_the_kernel_does(dtype):
    """P = 1: the step's update, drawn in leaf layout by counter_noise, lands
    on the parameters the kernel's (rows, LANE) replay of the same record
    gives, bit for bit."""
    params = jax.tree.map(lambda x: x.astype(dtype),
                          _tree(jax.random.PRNGKey(14)))
    new_p, _, (keys, coeffs) = zo.spsa_step(_quad, params,
                                            jax.random.PRNGKey(15),
                                            1e-3, 0.1, 1, dist="counter")
    assert maxdiff(new_p, params) > 0
    assert maxdiff(new_p, _rows_replay(params, keys, coeffs)) == 0.0


def test_counter_spsa_step_sums_its_records_before_rounding():
    """P = 2: one f32 sum of both records, rounded once."""
    params = _tree(jax.random.PRNGKey(16))
    new_p, _, (keys, coeffs) = zo.spsa_step(_quad, params,
                                            jax.random.PRNGKey(17),
                                            1e-3, 0.1, 2, dist="counter")
    want = _rows_replay(params, keys, coeffs)
    scale = max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(want))
    assert maxdiff(new_p, want) <= 1e-6 * scale


@pytest.mark.parametrize("n", [1, 8, 2048])
def test_zo_replay_leaf_auto_takes_the_xla_formula_on_tpu(monkeypatch, n):
    """impl='auto' sums the records in XLA's fusion and never reaches the
    Pallas kernel, on a TPU backend too, at any N."""
    import repro.kernels.ops as ops

    def no_kernel(*a, **k):
        raise AssertionError("zo_replay_flat reached on the auto path")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "zo_replay_flat", no_kernel)
    x = jax.random.normal(jax.random.PRNGKey(19), (5, 300), jnp.bfloat16)
    seeds, coeffs = _records(n, salt=19)
    got = ops.zo_replay_leaf(x, seeds, coeffs)
    assert maxdiff(got, ref.zo_replay_ref(x, seeds, coeffs)) == 0.0


def test_counter_replay_lowers_the_draw_once_per_leaf_shape():
    """Eight records into one leaf lower one copy of the counter draw and
    call it eight times; a copy per record made the lowered chunk program
    of internlm2-1.8b.silo 3.5 times as large."""
    x = jnp.zeros((5, 300), jnp.bfloat16)
    seeds, coeffs = _records(8, salt=20)
    text = jax.jit(ref.zo_replay_ref).lower(x, seeds, coeffs).as_text()
    assert text.count("stablehlo.cosine") == 1


# ---------------------------------------------------------------------------
# round level: seed_replay aggregation, fused vs scan
# ---------------------------------------------------------------------------

M = 2


@pytest.fixture(scope="module")
def round_setup():
    cfg = tiny_lm_cfg(dtype="float32")
    key = jax.random.PRNGKey(0)
    params = untie_params(cfg, init_params(cfg, key))
    batches = lm_batch(jax.random.PRNGKey(1), cfg, 2, 16, M=M)
    sfl = SFLConfig(n_clients=M, tau=2, cut_units=1,
                    perturbation_dist="counter")
    return cfg, params, batches, sfl


@pytest.mark.parametrize("client_mode", ["parallel", "sequential"])
def test_round_seed_replay_fused_matches_scan(round_setup, client_mode):
    """mu_splitfed_round(aggregation='seed_replay'): the one-pass fused
    replay must match the N-step scan replay (f32, summation order only)."""
    cfg, params, batches, sfl = round_setup
    mask = jnp.ones((M,), jnp.float32)
    rk = jax.random.PRNGKey(7)
    p_f, m_f = mu_splitfed_round(cfg, sfl, params, batches, mask, rk,
                                 client_mode=client_mode,
                                 aggregation="seed_replay", replay="fused")
    p_s, m_s = mu_splitfed_round(cfg, sfl, params, batches, mask, rk,
                                 client_mode=client_mode,
                                 aggregation="seed_replay", replay="scan")
    assert maxdiff(p_f, p_s) <= 1e-5
    assert jnp.allclose(m_f.loss, m_s.loss, atol=1e-6)
    assert maxdiff(p_f, params) > 0           # and it actually trained


def test_gas_seed_replay_matches_dense(round_setup):
    """GAS: replica-mean aggregation and record replay are the same update
    (sp_new − xs is exactly −Σ cᵢuᵢ), so the two must agree in f32."""
    cfg, params, batches, sfl = round_setup
    state = gas_init_state(cfg, sfl, params, batches)
    fresh = jnp.ones((M,), jnp.float32)
    rk = jax.random.PRNGKey(3)
    p_d, _, _ = gas_round(cfg, sfl, params, state, batches, fresh, rk,
                          aggregation="dense")
    p_r, _, _ = gas_round(cfg, sfl, params, state, batches, fresh, rk,
                          aggregation="seed_replay")
    assert maxdiff(p_d, p_r) <= 1e-5


def test_gas_rejects_unknown_aggregation(round_setup):
    cfg, params, batches, sfl = round_setup
    state = gas_init_state(cfg, sfl, params, batches)
    with pytest.raises(ValueError, match="aggregation"):
        gas_round(cfg, sfl, params, state, batches,
                  jnp.ones((M,), jnp.float32), jax.random.PRNGKey(0),
                  aggregation="bogus")
