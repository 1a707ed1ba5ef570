"""Baselines the paper compares against (§5):

  vanilla_splitfed_round : SplitFed with ZO but no unbalanced updates
                           (exactly MU-SplitFed at τ=1 — shared code path,
                           which is itself a correctness check).
  gas_round              : GAS-like asynchronous SFL — the server proceeds
                           with *stale buffered activations* for slow
                           clients instead of waiting. Staleness enters as a
                           fresh/stale mask from the wall-clock simulator;
                           an activation buffer is carried across rounds.
  fedavg_round           : first-order FedAvg (full model on every client,
                           E local AdamW/SGD steps) — the memory-comparison
                           and convergence baseline of Fig. 4 / §5.
  fedlora_round          : FedAvg + LoRA adapters (only (A,B) train/ship).

All rounds are pure jit-able functions; system effects (delays, staleness,
participation) are data inputs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SFLConfig
from repro.core import zo
from repro.core.splitfed import RoundMetrics, _client_round, mu_splitfed_round
from repro.models import (client_forward, loss_fn, merge_params,
                          server_forward, split_params)
from repro.optim import adamw_init, adamw_update, make_optimizer
from repro.optim.lora import apply_lora, init_lora

Params = Any


# ---------------------------------------------------------------------------
# vanilla SplitFed (τ=1, ZO)
# ---------------------------------------------------------------------------

def vanilla_splitfed_round(cfg: ModelConfig, sfl: SFLConfig, params: Params,
                           batches, active_mask, round_key, **kw):
    sfl1 = dataclasses.replace(sfl, tau=1)
    return mu_splitfed_round(cfg, sfl1, params, batches, active_mask,
                             round_key, **kw)


# ---------------------------------------------------------------------------
# GAS-like asynchronous SFL with an activation buffer
# ---------------------------------------------------------------------------

class GasState(NamedTuple):
    h_buffer: Any        # stacked (M, ...) last-seen unperturbed embeddings
    label_buffer: Any    # matching labels/batches for the stale activations


def gas_init_state(cfg: ModelConfig, sfl: SFLConfig, params: Params, batches):
    """Fill the buffer with an initial sweep (round 0 everyone is fresh)."""
    xc, _ = split_params(cfg, params, sfl.cut_units)
    h = jax.vmap(lambda b: client_forward(cfg, xc, b))(batches)
    return GasState(h_buffer=h, label_buffer=batches)


def gas_round(cfg: ModelConfig, sfl: SFLConfig, params: Params, state: GasState,
              batches, fresh_mask, round_key, *,
              aggregation: str = "dense",
              replay: str = "auto") -> Tuple[Params, GasState, RoundMetrics]:
    """fresh_mask (M,) f32: 1 = client delivered this round; 0 = straggler,
    server trains its replica from the buffered stale activation instead.
    Fresh clients also get the scalar ZO backprop; stale ones don't update
    their client side this round (they never received δ_c in time).

    aggregation='seed_replay' replays each client's server (key, coeff)
    records (and the client-side (ukey, ccoeff)) into the global halves via
    zo.fused_replay_updates instead of averaging dense replicas — the same
    compressed wire format as mu_splitfed_round."""
    if aggregation not in ("dense", "seed_replay"):
        raise ValueError(f"gas_round: unsupported aggregation "
                         f"{aggregation!r} (want 'dense' or 'seed_replay')")
    M = sfl.n_clients
    xc, xs = split_params(cfg, params, sfl.cut_units)
    mkeys = jax.vmap(lambda i: jax.random.fold_in(round_key, i))(jnp.arange(M))

    def per_client(b_new, b_old, h_old, k, fresh):
        ukey = jax.random.fold_in(k, 0)
        skey = jax.random.fold_in(k, 1)
        # fresh clients compute new messages; stale reuse the buffer
        with jax.named_scope("sfl.client_forward"):
            h_new = client_forward(cfg, xc, b_new)
            hp = client_forward(cfg, zo.perturb(xc, ukey, +sfl.zo_eps,
                                                sfl.perturbation_dist), b_new)
            hm = client_forward(cfg, zo.perturb(xc, ukey, -sfl.zo_eps,
                                                sfl.perturbation_dist), b_new)
        h = jax.tree.map(lambda a, o: jnp.where(fresh > 0, a, o), h_new, h_old)
        b_used = jax.tree.map(lambda a, o: jnp.where(fresh > 0, a, o),
                              b_new, b_old)
        with jax.named_scope("sfl.server_eval"):
            loss0 = server_forward(cfg, xs, h, b_used)

        def loss_of(sp):
            return server_forward(cfg, sp, h, b_used)
        with jax.named_scope("sfl.server_tau"):
            sp_new, delta, (skeys, scoeffs) = zo.spsa_step(
                loss_of, xs, skey, sfl.zo_eps, sfl.lr_server,
                sfl.n_perturbations, sfl.perturbation_dist, replay=replay)
        with jax.named_scope("sfl.zo_backprop"):
            delta_c = (server_forward(cfg, sp_new, hp, b_new)
                       - server_forward(cfg, sp_new, hm, b_new)
                       ).astype(jnp.float32)
        ccoeff = fresh * sfl.lr_client * delta_c / (2.0 * sfl.zo_eps)
        return {"xs_final": sp_new, "h": h, "b": b_used, "ukey": ukey,
                "ccoeff": ccoeff, "loss0": loss0, "delta": delta,
                "skeys": skeys, "scoeffs": scoeffs}

    out = jax.vmap(per_client)(batches, state.label_buffer, state.h_buffer,
                               mkeys, fresh_mask)
    w = jnp.full((M,), 1.0 / M, jnp.float32)

    with jax.named_scope("sfl.replay"):
        if aggregation == "dense":
            def agg(g, stacked):
                d = jnp.tensordot(w, (stacked - g[None]).astype(jnp.float32),
                                  axes=1)
                return (g + sfl.lr_global * d).astype(g.dtype)
            xs_new = jax.tree.map(agg, xs, out["xs_final"])
        else:  # seed_replay: the (M, P) server records, weighted η_g·w_m
            xs_new = zo.replay_weighted_records(
                xs, out["skeys"], out["scoeffs"], sfl.lr_global * w,
                sfl.perturbation_dist, impl=replay)
        xc_new = zo.replay_weighted_records(
            xc, out["ukey"], out["ccoeff"], sfl.lr_global * w,
            sfl.perturbation_dist, impl=replay)
    new_state = GasState(h_buffer=out["h"], label_buffer=out["b"])
    metrics = RoundMetrics(loss=out["loss0"],
                           server_deltas=out["delta"][:, None],
                           client_delta=out["ccoeff"])
    return merge_params(cfg, xc_new, xs_new), new_state, metrics


# ---------------------------------------------------------------------------
# FedAvg (first-order, full model on clients)
# ---------------------------------------------------------------------------

def fedavg_round(cfg: ModelConfig, params: Params, batches, active_mask,
                 lr: float, local_steps: int = 1, optimizer: str = "sgd",
                 eta_g: float = 1.0):
    """One FedAvg round: E local FO steps per client (vmapped), FedAvg agg.
    Local batches: leaves (M, E, b, S) when local_steps > 1 else (M, b, S)."""
    M = active_mask.shape[0]
    init_opt, update = make_optimizer(optimizer)
    grad_fn = jax.grad(lambda p, b: loss_fn(cfg, p, b))

    def local(b):
        def step(carry, bi):
            p, s = carry
            g = grad_fn(p, bi)
            p, s = update(p, g, s, lr)
            return (p, s), None
        bs = (jax.tree.map(lambda a: a[None], b) if local_steps == 1
              else b)
        (p_f, _), _ = jax.lax.scan(step, (params, init_opt(params)), bs)
        return p_f

    stacked = jax.vmap(local)(batches)
    wsum = jnp.maximum(jnp.sum(active_mask), 1.0)
    w = (active_mask / wsum).astype(jnp.float32)

    def agg(g, st):
        d = jnp.tensordot(w, (st - g[None]).astype(jnp.float32), axes=1)
        return (g + eta_g * d).astype(g.dtype)
    return jax.tree.map(agg, params, stacked)


# ---------------------------------------------------------------------------
# FedAvg + LoRA
# ---------------------------------------------------------------------------

def fedlora_round(cfg: ModelConfig, params: Params, lora, batches,
                  active_mask, lr: float, alpha: float = 16.0,
                  eta_g: float = 1.0):
    """Clients train only the LoRA adapters; only (A,B) are aggregated."""
    grad_fn = jax.grad(
        lambda lo, b: loss_fn(cfg, apply_lora(params, lo, alpha), b))

    def local(b):
        g = grad_fn(lora, b)
        return jax.tree.map(lambda x, gg: (x.astype(jnp.float32)
                                           - lr * gg.astype(jnp.float32)
                                           ).astype(x.dtype), lora, g)

    stacked = jax.vmap(local)(batches)
    wsum = jnp.maximum(jnp.sum(active_mask), 1.0)
    w = (active_mask / wsum).astype(jnp.float32)

    def agg(g, st):
        d = jnp.tensordot(w, (st - g[None]).astype(jnp.float32), axes=1)
        return (g + eta_g * d).astype(g.dtype)
    return jax.tree.map(agg, lora, stacked)
