"""Faults planted under the timed path, to show that the check catches
them: each is a context manager that patches the program for the runs
inside it and drops the compiled programs made before and under it.

  * ``unchanged``: every round returns the parameters it was given (the
    metrics are still computed);
  * ``half_batch``: the server's loss leaves out half of each client's
    batch and takes the mean over the rest: half of its rows, or of its
    positions where a client holds one row (the client forwards still run
    whole).

One chip has no exchange between chips, and a training round produces no
token or answer, so those faults have nothing to break here.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch")


def _reset():
    import jax
    from repro.core import engine
    engine.clear_algorithm_cache()
    jax.clear_caches()


@contextlib.contextmanager
def planted(name: str):
    from repro.core import engine, splitfed
    if name == "unchanged":
        where, attr = engine, "mu_splitfed_round"
        orig = engine.mu_splitfed_round

        def broken(cfg, sfl, params, *a, **k):
            return params, orig(cfg, sfl, params, *a, **k)[1]
    elif name == "half_batch":
        where, attr = splitfed, "server_forward"
        orig = splitfed.server_forward

        def broken(cfg, sp, h, batch, **k):
            B, S = batch["tokens"].shape
            cut = ((lambda v: v[:B // 2]) if B > 1
                   else (lambda v: v[:, :S // 2]))
            h = dict(h, h=cut(h["h"]))
            return orig(cfg, sp, h, {key: cut(v) for key, v in batch.items()},
                        **k)
    else:
        raise ValueError(f"fault {name!r}; known: {FAULTS}")
    _reset()
    setattr(where, attr, broken)
    try:
        yield
    finally:
        setattr(where, attr, orig)
        _reset()
