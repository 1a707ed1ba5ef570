"""MU-SplitFed round semantics: mode equivalences, τ=1 == vanilla,
participation masking, convergence on a tiny task."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import lm_batch, maxdiff, tiny_lm_cfg
from repro.configs import SFLConfig
from repro.core.baselines import vanilla_splitfed_round
from repro.core.splitfed import mu_splitfed_round
from repro.models import init_params, untie_params

M = 4


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_lm_cfg(dtype="float32")
    key = jax.random.PRNGKey(0)
    params = untie_params(cfg, init_params(cfg, key))
    batches = lm_batch(jax.random.PRNGKey(9), cfg, 2, 16, M=M)
    sfl = SFLConfig(n_clients=M, tau=3, cut_units=1)
    return cfg, params, batches, sfl


def test_parallel_equals_sequential(setup):
    cfg, params, batches, sfl = setup
    mask = jnp.ones((M,), jnp.float32)
    rk = jax.random.PRNGKey(7)
    p1, m1 = mu_splitfed_round(cfg, sfl, params, batches, mask, rk,
                               client_mode="parallel")
    p2, m2 = mu_splitfed_round(cfg, sfl, params, batches, mask, rk,
                               client_mode="sequential")
    assert maxdiff(p1, p2) < 1e-5
    assert jnp.allclose(m1.loss, m2.loss, atol=1e-5)


def test_dense_equals_seed_replay_f32(setup):
    """Eq. 7 dense aggregation == compressed seed-replay aggregation (exact
    in f32 up to summation order)."""
    cfg, params, batches, sfl = setup
    mask = jnp.ones((M,), jnp.float32)
    rk = jax.random.PRNGKey(7)
    p1, _ = mu_splitfed_round(cfg, sfl, params, batches, mask, rk,
                              aggregation="dense")
    p2, _ = mu_splitfed_round(cfg, sfl, params, batches, mask, rk,
                              aggregation="seed_replay")
    assert maxdiff(p1, p2) < 1e-5


def test_tau1_equals_vanilla_splitfed(setup):
    """Vanilla SplitFed is exactly MU-SplitFed at τ=1 (paper §5 baseline)."""
    cfg, params, batches, _ = setup
    sfl1 = SFLConfig(n_clients=M, tau=1, cut_units=1)
    sfl9 = SFLConfig(n_clients=M, tau=9, cut_units=1)  # tau ignored by vanilla
    mask = jnp.ones((M,), jnp.float32)
    rk = jax.random.PRNGKey(11)
    p1, _ = mu_splitfed_round(cfg, sfl1, params, batches, mask, rk)
    p2, _ = vanilla_splitfed_round(cfg, sfl9, params, batches, mask, rk)
    assert maxdiff(p1, p2) == 0.0


def test_inactive_clients_do_not_contribute(setup):
    """With only client 0 active, the update must be independent of the
    other clients' data."""
    cfg, params, batches, sfl = setup
    mask = jnp.zeros((M,), jnp.float32).at[0].set(1.0)
    rk = jax.random.PRNGKey(13)
    p1, _ = mu_splitfed_round(cfg, sfl, params, batches, mask, rk)
    scrambled = jax.tree.map(
        lambda a: a.at[1:].set(jnp.flip(a[1:], axis=-1)), batches)
    p2, _ = mu_splitfed_round(cfg, sfl, params, scrambled, mask, rk)
    assert maxdiff(p1, p2) < 1e-6


def test_tau_amortizes_progress(setup):
    """More server steps per round (higher τ) should move the server-side
    parameters further per communication round."""
    cfg, params, batches, _ = setup
    mask = jnp.ones((M,), jnp.float32)
    rk = jax.random.PRNGKey(17)

    def server_movement(tau):
        sfl = SFLConfig(n_clients=M, tau=tau, cut_units=1,
                        lr_server=1e-3, lr_client=5e-4)
        p, _ = mu_splitfed_round(cfg, sfl, params, batches, mask, rk)
        from repro.models import split_params
        _, s0 = split_params(cfg, params, 1)
        _, s1 = split_params(cfg, p, 1)
        return sum(float(jnp.sum(jnp.square(a - b)))
                   for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)))

    assert server_movement(8) > server_movement(1)


def test_loss_decreases_over_rounds():
    cfg = tiny_lm_cfg(dtype="float32", vocab_size=32)
    key = jax.random.PRNGKey(1)
    params = untie_params(cfg, init_params(cfg, key))
    sfl = SFLConfig(n_clients=2, tau=2, cut_units=1,
                    lr_server=5e-3, lr_client=1e-3, lr_global=1.0)
    batches = lm_batch(jax.random.PRNGKey(2), cfg, 2, 16, M=2)
    mask = jnp.ones((2,), jnp.float32)
    round_fn = jax.jit(lambda p, k: mu_splitfed_round(
        cfg, sfl, p, batches, mask, k))
    losses = []
    for r in range(30):
        params, m = round_fn(params, jax.random.fold_in(key, r))
        losses.append(float(m.loss.mean()))
    assert (sum(losses[-5:]) / 5) < (sum(losses[:5]) / 5), losses


PHASES = ("sfl.client_forward", "sfl.server_eval", "sfl.server_tau",
          "sfl.zo_backprop", "sfl.replay")


@pytest.mark.parametrize("aggregation", ["seed_replay", "dense"])
@pytest.mark.parametrize("client_mode", ["parallel", "sequential"])
def test_round_phases_are_named_in_the_compiled_program(setup, client_mode,
                                                        aggregation):
    """Each step of Algorithm 1 leaves its named scope in the op_name
    metadata of the compiled round, which is what a device trace carries."""
    cfg, params, batches, sfl = setup
    mask = jnp.ones((M,), jnp.float32)
    f = jax.jit(lambda p, b, m, k: mu_splitfed_round(
        cfg, sfl, p, b, m, k, client_mode=client_mode,
        aggregation=aggregation))
    hlo = f.lower(params, batches, mask,
                  jax.random.PRNGKey(7)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in PHASES:
        # a path component, or one under a transform: vmap(sfl.replay)
        under = re.compile(rf"[/(]{re.escape(scope)}[/)]")
        assert any(under.search(n) for n in op_names), scope


@pytest.mark.parametrize("kind", ["sync", "async", "async_sparse", "gas"])
def test_every_round_splits_behind_a_barrier(setup, kind):
    """Each round that splits the tree, runs its clients and replays into
    the halves splits through split_halves, whose optimization barrier
    keeps XLA from holding the whole tree live beside the halves."""
    from repro.core import events
    from repro.core.baselines import gas_init_state, gas_round
    cfg, params, batches, sfl = setup
    ones, key = jnp.ones((M,), jnp.float32), jax.random.PRNGKey(7)
    if kind == "sync":
        fn = lambda p: mu_splitfed_round(cfg, sfl, p, batches, ones, key)
    elif kind == "async":
        store = events.init_store(sfl)
        fn = lambda p: events.async_mu_splitfed_step(
            cfg, sfl, p, store, batches, ones, ones / M, key)
    elif kind == "async_sparse":
        sp = dataclasses.replace(sfl, timeline="sparse")
        store, ids = events.init_store(sp), jnp.arange(M)
        fn = lambda p: events.async_mu_splitfed_sparse_step(
            cfg, sp, p, store, batches, ids, ids, ids, ones / M, key)
    else:
        state = gas_init_state(cfg, sfl, params, batches)
        fn = lambda p: gas_round(cfg, sfl, p, state, batches, ones, key)
    assert "optimization_barrier" in str(jax.make_jaxpr(fn)(params))


def test_compile_cache_keeps_each_programs_scopes(tmp_path, monkeypatch):
    """Two programs that differ only in a named scope get executables of
    their own from the persistent cache, so a trace never shows an earlier
    version's op names."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.compile_cache import enable_compile_cache

    def make(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x) * 2
        return f

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        enable_compile_cache()
        compilation_cache.reset_cache()
        x = jnp.ones((8, 8))
        for scope in ("sfl.server_eval", "sfl.zo_backprop"):
            hlo = jax.jit(make(scope)).lower(x).compile().as_text()
            assert f"/{scope}/" in hlo, scope
        assert len(list(tmp_path.iterdir())) >= 2     # both were cached
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
