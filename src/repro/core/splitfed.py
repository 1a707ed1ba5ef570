"""MU-SplitFed: the paper's unbalanced-update Split Federated round
(Algorithm 1), plus the M=1 MU-Split special case.

One global round t:
  Phase 1 (per client m, in parallel):
    client:  u_m ~ key; send H_m = {h, h+, h-}  (three client forwards)
    server:  τ local ZO steps on the *stale* unperturbed h (Eq. 5) —
             x_{s,m}^{t,i+1} = x_{s,m}^{t,i} − η_s (δ_i/2λ) u_i
    server:  δ_c,m = F(x_{s,m}^{t,τ}, h+) − F(x_{s,m}^{t,τ}, h−)   (Eq. 6)
             → one scalar back to the client
    client:  x_{c,m}^{t+1} = x_c^t − η_c (δ_c,m/2λ) u_m
  Phase 2:  dual aggregation (Eq. 7) with global lr η_g.

Execution modes (planner-chosen; both lower the same math):
  client_mode='parallel'    vmap over M — per-client server replicas stacked
                            (M, …), M mapped to the mesh 'data' axis.
  client_mode='sequential'  lax.scan over M — one working server copy
                            (FSDP'd over the whole mesh); for archs whose
                            M replicas cannot fit HBM.
Aggregation modes:
  'dense'        Eq. 7 literally — param-sized mean over M (all-reduce).
  'seed_replay'  beyond-paper: replay the (key, δ)-records of every client
                 directly into the global params — only O(Mτ P) scalars
                 cross the aggregation axis (paper Appendix A realized as a
                 collective-compression scheme). The records are applied
                 through zo.fused_replay_updates: with dist='counter' all
                 N = Mτ P contributions are summed in float32 by one XLA
                 fusion per leaf, one parameter sweep (ladder v4), instead
                 of an N-step scan (``replay`` selects the path; 'scan'
                 keeps the v3 behaviour).

The round function is pure/jit-able; straggler wall-clock simulation and
participation decisions live outside (core/straggler.py) and enter here only
through ``active_mask``.

Each step of the round runs under a ``jax.named_scope`` that lands in the
op_name metadata of its compiled ops, so a device trace splits a round's
time by step: ``sfl.client_forward`` (the three client forwards and both
perturbations), ``sfl.server_eval`` (the round-start loss), ``sfl.server_tau``
(the τ SPSA steps), ``sfl.zo_backprop`` (the δ_c pair of server forwards)
and ``sfl.replay`` (the aggregation). The async steps (core/events.py) and
GAS (core/baselines.py) use the same five names.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SFLConfig
from repro.core import zo
from repro.models import client_forward, merge_params, server_forward, split_params

Params = Any


class RoundMetrics(NamedTuple):
    loss: jax.Array          # (M,) round-start loss per client (f32)
    server_deltas: jax.Array  # (M, tau) mean SPSA deltas on the server
    client_delta: jax.Array  # (M,) scalar ZO-backprop differences


# ---------------------------------------------------------------------------
# per-client phases
# ---------------------------------------------------------------------------

def _client_messages(cfg: ModelConfig, sfl: SFLConfig, xc: Params, batch,
                     ukey):
    """Three client forwards -> (h, h+, h-). The perturbation u_m never
    leaves the client; only its key is kept for the later update."""
    with jax.named_scope("sfl.client_forward"):
        h = client_forward(cfg, xc, batch)
        hp = client_forward(cfg, zo.perturb(xc, ukey, +sfl.zo_eps,
                                            sfl.perturbation_dist), batch)
        hm = client_forward(cfg, zo.perturb(xc, ukey, -sfl.zo_eps,
                                            sfl.perturbation_dist), batch)
    return h, hp, hm


def split_halves(cfg: ModelConfig, params: Params, cut_units: int):
    """(client half, server half) of a round's parameters, behind an
    optimization barrier, so that every use reads the halves' own copies.
    Without it XLA fuses the split's slices into the replay fusions at the
    round's end, which keeps the whole parameter tree live through the
    client work beside the copies: on a v5e, internlm2-1.8b's chunk
    program takes 18.5 GiB against 13.3 with sequential clients, and the
    sparse-async one 17.9 against 13.9. Every round that splits, runs its
    clients and replays into the halves splits here."""
    return jax.lax.optimization_barrier(split_params(cfg, params, cut_units))


def _server_tau_steps(cfg: ModelConfig, sfl: SFLConfig, xs: Params, h, batch,
                      skey):
    """τ unbalanced ZO steps on the stale embedding h. Returns
    (xs_final, deltas (τ,), records (keys (τ,P), coeffs (τ,P)))."""
    def loss_of(sp):
        return server_forward(cfg, sp, h, batch)

    def step(sp, i):
        k_i = jax.random.fold_in(skey, i)
        sp, mean_delta, (pkeys, coeffs) = zo.spsa_step(
            loss_of, sp, k_i, sfl.zo_eps, sfl.lr_server,
            sfl.n_perturbations, sfl.perturbation_dist)
        return sp, (mean_delta, pkeys, coeffs)

    with jax.named_scope("sfl.server_tau"):
        xs_f, (deltas, keys, coeffs) = jax.lax.scan(step, xs,
                                                    jnp.arange(sfl.tau))
    return xs_f, deltas, (keys, coeffs)


def _client_round(cfg: ModelConfig, sfl: SFLConfig, xc: Params, xs: Params,
                  batch, mkey, eval_loss: bool = True):
    """Full per-client round. Returns per-client results."""
    ukey = jax.random.fold_in(mkey, 0)
    skey = jax.random.fold_in(mkey, 1)
    h, hp, hm = _client_messages(cfg, sfl, xc, batch, ukey)
    with jax.named_scope("sfl.server_eval"):
        loss0 = (server_forward(cfg, xs, h, batch) if eval_loss
                 else jnp.zeros((), jnp.float32))      # round-start metric
    xs_f, deltas, records = _server_tau_steps(cfg, sfl, xs, h, batch, skey)
    # ZO backprop (Eq. 6): scalar from the *final* server model
    with jax.named_scope("sfl.zo_backprop"):
        delta_c = (server_forward(cfg, xs_f, hp, batch)
                   - server_forward(cfg, xs_f, hm, batch)
                   ).astype(jnp.float32)
    # client update coeff: η_c · δ_c / (2λ); u replayed from ukey
    ccoeff = sfl.lr_client * delta_c / (2.0 * sfl.zo_eps)
    return {
        "xs_final": xs_f,
        "deltas": deltas,
        "srv_keys": records[0], "srv_coeffs": records[1],
        "ukey": ukey, "ccoeff": ccoeff,
        "loss0": loss0,
    }


# ---------------------------------------------------------------------------
# the global round
# ---------------------------------------------------------------------------

def mu_splitfed_round(cfg: ModelConfig, sfl: SFLConfig, params: Params,
                      batches, active_mask, round_key, *,
                      client_mode: str = "parallel",
                      aggregation: str = "dense",
                      replay: str = "auto",
                      eval_loss: bool = True
                      ) -> Tuple[Params, RoundMetrics]:
    """One global round. ``batches`` leaves have leading M dim;
    ``active_mask`` is (M,) f32 participation weights (0 = straggler dropped /
    not sampled). ``replay`` ('auto'|'fused'|'scan') selects how replayable
    records are applied — see zo.fused_replay_updates. Returns
    (new_params, metrics)."""
    M = sfl.n_clients
    xc, xs = split_halves(cfg, params, sfl.cut_units)
    mkeys = jax.vmap(lambda i: jax.random.fold_in(round_key, i))(jnp.arange(M))
    wsum = jnp.maximum(jnp.sum(active_mask), 1.0)
    w = (active_mask / wsum).astype(jnp.float32)        # (M,) aggregation wts

    if client_mode == "parallel":
        out = jax.vmap(lambda b, k: _client_round(cfg, sfl, xc, xs, b, k,
                                                  eval_loss)
                       )(batches, mkeys)
    elif client_mode == "sequential":
        def body(carry, xs_in):
            acc = carry
            b, k, wm = xs_in
            r = _client_round(cfg, sfl, xc, xs, b, k, eval_loss)
            if aggregation == "dense":
                with jax.named_scope("sfl.replay"):
                    acc = jax.tree.map(
                        lambda a, f, g: a + wm * (f - g).astype(jnp.float32),
                        acc, r["xs_final"], xs)
            light = {k2: r[k2] for k2 in
                     ("deltas", "srv_keys", "srv_coeffs", "ukey", "ccoeff",
                      "loss0")}
            return acc, light
        acc0 = (jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), xs)
                if aggregation == "dense" else jnp.zeros(()))
        acc, out = jax.lax.scan(body, acc0, (batches, mkeys, w))
    else:
        raise ValueError(client_mode)

    with jax.named_scope("sfl.replay"):
        if aggregation != "dense":
            # seed_replay: flatten (M, τ, P) records, weight by η_g·w_m
            xs_new = zo.replay_weighted_records(
                xs, out["srv_keys"], out["srv_coeffs"], sfl.lr_global * w,
                sfl.perturbation_dist, impl=replay)
        elif client_mode == "parallel":
            # Eq. 7: x_s' = x_s + η_g Σ w_m (x_{s,m}^τ − x_s)
            def agg(g, stacked):
                delta = jnp.tensordot(
                    w, (stacked - g[None]).astype(jnp.float32), axes=1)
                return (g + sfl.lr_global * delta).astype(g.dtype)
            xs_new = jax.tree.map(agg, xs, out["xs_final"])
        else:
            xs_new = jax.tree.map(
                lambda g, a: (g + sfl.lr_global * a).astype(g.dtype), xs, acc)
        # client aggregation — always replayable (Eq. 7 left): the
        # per-client update is rank-one in u_m, so Σ_m w_m Δ_m is Σ of
        # replayed records.
        xc_new = zo.replay_weighted_records(
            xc, out["ukey"], out["ccoeff"], sfl.lr_global * w,
            sfl.perturbation_dist, impl=replay)

    metrics = RoundMetrics(loss=out["loss0"], server_deltas=out["deltas"],
                           client_delta=out["ccoeff"])
    return merge_params(cfg, xc_new, xs_new), metrics


def mu_split_round(cfg: ModelConfig, sfl: SFLConfig, params: Params, batch,
                   round_key) -> Tuple[Params, RoundMetrics]:
    """MU-Split: the single-client (M=1, SL) special case of Sec. 4.1."""
    sfl1 = (sfl if sfl.n_clients == 1
            else dataclasses.replace(sfl, n_clients=1))
    batches = jax.tree.map(lambda a: a[None], batch)
    return mu_splitfed_round(cfg, sfl1, params, batches,
                             jnp.ones((1,), jnp.float32), round_key)
