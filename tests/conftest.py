"""Shared fixtures. NOTE: no XLA_FLAGS here — tests run on 1 CPU device by
design (the 512-device setting belongs exclusively to repro.launch.dryrun)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture
def jax_sanitizers(monkeypatch):
    """Runtime backstop for repro.analysis's host-sync rule (opt in with
    ``pytestmark = pytest.mark.usefixtures("jax_sanitizers")``).

    Two sanitizers for the duration of the test:

    * ``jax_numpy_rank_promotion="raise"`` — implicit rank promotion in
      any jnp op becomes an error instead of a silent broadcast;
    * every executable minted by the engine's ``_cached_jit`` registry
      dispatches under ``jax.transfer_guard("disallow")`` — an argument
      reaching the jit boundary that is not already device-committed
      (stray numpy row, python scalar) trips an implicit host-to-device
      transfer error. Host staging around the call (jnp.asarray uploads,
      the per-chunk np.asarray flush) is explicit and stays legal, so
      this pins exactly the invariant: no *implicit* transfers inside
      the engine's scan/stream loop.
    """
    from repro.core import engine as _engine
    orig_cached_jit = _engine._cached_jit

    def guarded_cached_jit(algo, mode, cfg, sfl, build):
        fn, built = orig_cached_jit(algo, mode, cfg, sfl, build)

        def dispatch(*args, **kwargs):
            with jax.transfer_guard("disallow"):
                return fn(*args, **kwargs)
        return dispatch, built

    monkeypatch.setattr(_engine, "_cached_jit", guarded_cached_jit)
    old = jax.config.jax_numpy_rank_promotion or "allow"
    jax.config.update("jax_numpy_rank_promotion", "raise")
    try:
        yield
    finally:
        jax.config.update("jax_numpy_rank_promotion", old)


def tiny_lm_cfg(**kw):
    """A minimal dense config for algorithm tests (fast compiles)."""
    from repro.configs import get_config
    base = dict(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                vocab_size=64, max_seq_len=64)
    base.update(kw)
    return get_config("olmo-1b", smoke=True).replace(**base)


def lm_batch(key, cfg, B, S, M=None):
    shape = (M, B, S) if M else (B, S)
    toks = jax.random.randint(key, shape, 0, cfg.vocab_size)
    return {"tokens": toks, "labels": toks}


def maxdiff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
