"""Unified algorithm engine: one driver, every algorithm, rounds fused
on-device.

The paper's headline claim is wall-clock (rounds over *time*), yet the
historical drivers executed rounds one Python iteration at a time — each
paying a dispatch, a host sync, and an un-donated parameter copy per round,
and each hand-rolling its own loop + algorithm special cases. This module
replaces all of them:

  Algorithm    protocol (init_state / round_fn / time_model / metrics_spec)
               with registered adapters for mu_splitfed, vanilla, gas,
               fedavg, and fedlora — every algorithm is a pure
               (params, state, batch, mask, key) -> (params, state, metrics)
               round, so the driver is algorithm-agnostic (GAS state
               threading included).
  run_rounds   the driver. mode='scan' (default) lifts the loop into a
               chunked, jit'd jax.lax.scan over rounds with params/state
               DONATED across chunks: straggler delays, participation /
               deadline masks (straggler.make_schedule) and per-round
               fold-in keys are precomputed on host as stacked (R, M) /
               (R, 2) arrays and scanned as data; metrics are stacked per
               chunk and flushed to host only at chunk boundaries — which
               is also where checkpointing hooks in. mode='python' keeps
               the legacy one-jit-call-per-round loop as the equivalence
               baseline (benchmarks/bench_rounds.py gates scan == python
               on the loss trajectory; perf ladder rung v5). mode='async'
               scans the compiled event timeline instead (core/events.py):
               quorum-committed server versions, the in-flight seed-record
               buffer carried as engine state, staleness-discounted fused
               replay — rung v6, gated async == scan at full quorum.
  Controller   chunk-boundary policy hook: ``update(round_idx, window,
               metrics) -> {sfl field: value}``. AdaptiveTau is the
               paper's "adaptive tuning of τ" — it re-plans τ from the
               observed straggler gap via straggler.plan_tau; a τ change
               re-jits the round body, amortized across chunks by the
               per-algo executable cache.

Chunk boundaries are aligned to ckpt_every, so a run killed after chunk k
resumes from its checkpoint onto the *same* round boundaries — with
stateless data order and precomputed schedules the resumed trajectory is
bit-identical to an uninterrupted run (tests/test_engine.py). Stateful
algorithms (GAS activation buffer, FedLoRA adapters) checkpoint their
engine state alongside params as a {'params','state'} bundle; restore_run
resumes them exactly. Controller runs additionally record the overrides in
effect and the controller's own state in the checkpoint metadata —
apply_resume_overrides replays them, so a resumed adaptive-τ run continues
at the adapted τ/η_s with its EMA intact (the first post-resume chunk has
no observed window and keeps the restored τ, so such runs are exact up to
that one skipped re-plan).
"""
from __future__ import annotations

import dataclasses
from time import perf_counter
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Protocol,
                    Tuple, Union, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SFLConfig
from repro.core import events
from repro.core import straggler as strag
from repro.obs.telemetry import RoundTelemetry, TelemetrySink
from repro.obs.trace import span
from repro.core.baselines import (fedavg_round, fedlora_round, gas_init_state,
                                  gas_round, vanilla_splitfed_round)
from repro.core.splitfed import mu_splitfed_round

Params = Any
State = Any
Batch = Dict[str, Any]
MetricsDict = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# the Algorithm protocol + registry
# ---------------------------------------------------------------------------

@runtime_checkable
class Algorithm(Protocol):
    """One federated algorithm as the engine sees it.

    round_fn must be pure/jit-able; all system effects (delays, staleness,
    participation) enter as the (M,) mask data row. State is an arbitrary
    pytree carried across rounds (empty tuple for stateless algorithms).
    """
    name: str

    def init_state(self, cfg: ModelConfig, sfl: SFLConfig, params: Params,
                   batch0: Batch) -> State: ...

    def round_fn(self, cfg: ModelConfig, sfl: SFLConfig, params: Params,
                 state: State, batch: Batch, mask: jax.Array, key: jax.Array
                 ) -> Tuple[Params, State, MetricsDict]: ...

    def time_model(self, delays: np.ndarray, mask: np.ndarray,
                   sfl: SFLConfig, sched: strag.Schedule) -> float: ...

    def metrics_spec(self, cfg: ModelConfig, sfl: SFLConfig
                     ) -> Dict[str, Tuple[int, ...]]: ...


ALGORITHMS: Dict[str, Callable[..., Algorithm]] = {}
_INSTANCES: Dict[Tuple[str, Tuple], Algorithm] = {}


def register(cls):
    ALGORITHMS[cls.name] = cls
    # a re-registration must not leave get_algorithm serving memoized
    # instances of the previous class under the same name
    for k in [k for k in _INSTANCES if k[0] == cls.name]:
        del _INSTANCES[k]
    return cls


def get_algorithm(name: Union[str, Algorithm], **opts) -> Algorithm:
    """Resolve an algorithm by registry name or pass a ready-made Algorithm
    instance through.

    By-name resolution is MEMOIZED on (name, opts): repeated calls return
    the same adapter instance, so the engine's per-instance jit cache
    (keyed on mode/cfg/sfl) survives across run_rounds calls — a benchmark
    sweep re-running the same configuration hits the compiled executables
    instead of re-tracing a fresh adapter every run
    (tests/test_engine.py counts the traces)."""
    if isinstance(name, str):
        if name not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {name!r}; "
                             f"registered: {sorted(ALGORITHMS)}")
        k = (name, tuple(sorted(opts.items())))
        try:
            hash(k)
        except TypeError:               # unhashable opt values: no memo
            return ALGORITHMS[name](**opts)
        if k not in _INSTANCES:
            _INSTANCES[k] = ALGORITHMS[name](**opts)
        return _INSTANCES[k]
    if opts:
        raise ValueError("opts only apply when resolving by name")
    return name


def clear_algorithm_cache() -> None:
    """Drop all memoized adapter instances (and with them their per-instance
    compiled-executable caches). Long-lived processes sweeping many distinct
    (cfg, sfl) configurations can call this between sweeps to release the
    retained executables."""
    _INSTANCES.clear()


class AlgorithmBase:
    """Shared defaults: stateless, standard mask row, per-client loss."""

    def init_state(self, cfg, sfl, params, batch0) -> State:
        return ()

    def round_mask(self, sched: strag.Schedule, r: int) -> np.ndarray:
        """The (M,) mask row round r's round_fn consumes (GAS overrides
        with its freshness rule)."""
        return sched.masks[r % sched.n_rounds]

    def metrics_spec(self, cfg, sfl) -> Dict[str, Tuple[int, ...]]:
        return {"loss": (sfl.n_clients,)}


@register
class MuSplitFed(AlgorithmBase):
    """The paper's τ-unbalanced split federated round (Algorithm 1)."""
    name = "mu_splitfed"

    def __init__(self, client_mode: str = "parallel",
                 aggregation: str = "dense", replay: str = "auto",
                 eval_loss: bool = True):
        self.client_mode = client_mode
        self.aggregation = aggregation
        self.replay = replay
        self.eval_loss = eval_loss

    def round_fn(self, cfg, sfl, params, state, batch, mask, key):
        params, m = mu_splitfed_round(
            cfg, sfl, params, batch, mask, key, client_mode=self.client_mode,
            aggregation=self.aggregation, replay=self.replay,
            eval_loss=self.eval_loss)
        return params, state, {"loss": m.loss, "server_deltas": m.server_deltas,
                               "client_delta": m.client_delta}

    def time_model(self, delays, mask, sfl, sched):
        return strag.round_time_mu_splitfed(delays, mask, sched.t_server,
                                            sfl.tau, sched.comm_for(mask))

    def metrics_spec(self, cfg, sfl):
        M = sfl.n_clients
        return {"loss": (M,), "server_deltas": (M, sfl.tau),
                "client_delta": (M,)}


@register
class VanillaSplitFed(MuSplitFed):
    """SplitFed without unbalanced updates — exactly MU-SplitFed at τ=1."""
    name = "vanilla"

    def round_fn(self, cfg, sfl, params, state, batch, mask, key):
        params, m = vanilla_splitfed_round(
            cfg, sfl, params, batch, mask, key, client_mode=self.client_mode,
            aggregation=self.aggregation, replay=self.replay,
            eval_loss=self.eval_loss)
        return params, state, {"loss": m.loss, "server_deltas": m.server_deltas,
                               "client_delta": m.client_delta}

    def time_model(self, delays, mask, sfl, sched):
        return strag.round_time_vanilla(delays, mask, sched.t_server,
                                        sched.comm_for(mask))

    def metrics_spec(self, cfg, sfl):
        return {"loss": (sfl.n_clients,), "server_deltas": (sfl.n_clients, 1),
                "client_delta": (sfl.n_clients,)}


@register
class AsyncMuSplitFed(MuSplitFed):
    """Semi-async MU-SplitFed over the compiled event timeline
    (core/events.py): the server commits a version as soon as a quorum of
    contributions has arrived; late arrivals fold into a later commit
    with a staleness discount, applied through the fused seed-replay path.
    Run it with ``mode='async'`` — the quorum / discount knobs live in
    SFLConfig (``quorum``, ``staleness_discount``). Under the sync modes
    ('scan'/'python') it degenerates to MU-SplitFed with seed-replay
    aggregation (its record store rides along untouched). Seed replay is
    not optional here: the in-flight buffer IS the (key, coeff) wire
    format — dense aggregation would mean buffering param-sized server
    trees per client — so anything but aggregation='seed_replay' is
    rejected rather than silently ignored."""
    name = "async_mu_splitfed"

    def __init__(self, client_mode: str = "parallel",
                 aggregation: str = "seed_replay", replay: str = "auto",
                 eval_loss: bool = True):
        if client_mode != "parallel":
            raise ValueError("async_mu_splitfed: the event-driven store "
                             "needs stacked per-client replicas "
                             "(client_mode='parallel')")
        if aggregation != "seed_replay":
            raise ValueError("async_mu_splitfed: the record store is the "
                             "seed-replay wire format; aggregation "
                             f"{aggregation!r} is not replayable")
        super().__init__(client_mode=client_mode, aggregation=aggregation,
                         replay=replay, eval_loss=eval_loss)

    def init_state(self, cfg, sfl, params, batch0):
        return events.init_store(sfl)

    def async_round_fn(self, cfg, sfl, params, store, batch, start_mask,
                       apply_w, key):
        return events.async_mu_splitfed_step(
            cfg, sfl, params, store, batch, start_mask, apply_w, key,
            replay=self.replay, eval_loss=self.eval_loss)

    def async_sparse_round_fn(self, cfg, sfl, params, store, batch,
                              start_client, start_slot, apply_slot,
                              apply_w, key):
        return events.async_mu_splitfed_sparse_step(
            cfg, sfl, params, store, batch, start_client, start_slot,
            apply_slot, apply_w, key, replay=self.replay,
            eval_loss=self.eval_loss)

    def time_model(self, delays, mask, sfl, sched):
        # event arrival times, not round maxima: the version ends at the
        # last pending ARRIVAL (delay + that client's own uplink), floored
        # by the τ·t_server server work. quorum=0 deliberately: this
        # per-row model is only consulted by the sync fallback modes,
        # which execute the full barrier and apply every contribution —
        # charging the K-th arrival there would understate the wait.
        # Quorum pacing is exact only with cross-version busy state, which
        # is what mode='async' reads off the compiled timeline instead.
        return events.quorum_round_time(delays, mask, sched.t_server,
                                        sfl.tau, quorum=0,
                                        t_comm=sched.t_comm,
                                        t_comm_scale=sched.t_comm_scale)

    def metrics_spec(self, cfg, sfl):
        if getattr(sfl, "timeline", "dense") == "sparse":
            return {"loss": (events.resolve_store_geometry(sfl)[0],)}
        return {"loss": (sfl.n_clients,)}


@register
class Gas(AlgorithmBase):
    """GAS-like async SFL with a carried activation buffer. ``fresh``
    selects where the freshness mask comes from: 'mask' (the schedule's
    participation·deadline row — the training driver's convention) or
    'median' (clients at/below the per-round median delay — Fig. 2)."""
    name = "gas"

    def __init__(self, aggregation: str = "dense", replay: str = "auto",
                 fresh: str = "mask"):
        if fresh not in ("mask", "median"):
            raise ValueError(f"gas: fresh must be 'mask'|'median', "
                             f"got {fresh!r}")
        self.aggregation = aggregation
        self.replay = replay
        self.fresh = fresh

    def init_state(self, cfg, sfl, params, batch0):
        return gas_init_state(cfg, sfl, params, batch0)

    def round_mask(self, sched, r):
        i = r % sched.n_rounds
        return (sched.fresh_median[i] if self.fresh == "median"
                else sched.masks[i])

    def round_fn(self, cfg, sfl, params, state, batch, mask, key):
        params, state, m = gas_round(cfg, sfl, params, state, batch, mask,
                                     key, aggregation=self.aggregation,
                                     replay=self.replay)
        return params, state, {"loss": m.loss, "server_deltas": m.server_deltas,
                               "client_delta": m.client_delta}

    def time_model(self, delays, mask, sfl, sched):
        return strag.round_time_gas(delays, mask, sched.t_server, sched.t_gen,
                                    sched.comm_for(mask))

    def metrics_spec(self, cfg, sfl):
        return {"loss": (sfl.n_clients,), "server_deltas": (sfl.n_clients, 1),
                "client_delta": (sfl.n_clients,)}


@register
class FedAvg(AlgorithmBase):
    """First-order FedAvg (full model on every client, E local steps)."""
    name = "fedavg"

    def __init__(self, lr: Optional[float] = None, local_steps: int = 1,
                 optimizer: str = "sgd"):
        self.lr = lr
        self.local_steps = local_steps
        self.optimizer = optimizer

    def round_fn(self, cfg, sfl, params, state, batch, mask, key):
        from repro.models import loss_fn
        first = (jax.tree.map(lambda a: a[:, 0], batch)
                 if self.local_steps > 1 else batch)
        loss0 = jax.vmap(lambda b: loss_fn(cfg, params, b))(first)
        params = fedavg_round(cfg, params, batch, mask,
                              self.lr if self.lr is not None else sfl.lr_client,
                              self.local_steps, self.optimizer,
                              eta_g=sfl.lr_global)
        return params, state, {"loss": loss0.astype(jnp.float32)}

    def time_model(self, delays, mask, sfl, sched):
        return strag.round_time_local_only(delays, mask, sched.comm_for(mask))


@register
class FedLora(FedAvg):
    """FedAvg over LoRA adapters only; the base params never move — the
    adapter tree is the engine state."""
    name = "fedlora"

    def __init__(self, rank: int = 4, alpha: float = 16.0,
                 lr: Optional[float] = None):
        super().__init__(lr=lr)
        self.rank = rank
        self.alpha = alpha

    def init_state(self, cfg, sfl, params, batch0):
        from repro.optim.lora import init_lora
        return init_lora(cfg, params, self.rank,
                         jax.random.PRNGKey(sfl.seed))

    def round_fn(self, cfg, sfl, params, state, batch, mask, key):
        from repro.models import loss_fn
        from repro.optim.lora import apply_lora
        merged = apply_lora(params, state, self.alpha)
        loss0 = jax.vmap(lambda b: loss_fn(cfg, merged, b))(batch)
        lora = fedlora_round(cfg, params, state, batch, mask,
                             self.lr if self.lr is not None else sfl.lr_client,
                             self.alpha, eta_g=sfl.lr_global)
        return params, lora, {"loss": loss0.astype(jnp.float32)}


# ---------------------------------------------------------------------------
# chunk-boundary controllers (adaptive τ / deadline policies)
# ---------------------------------------------------------------------------

class SchedWindow(NamedTuple):
    """What a Controller observes at a chunk boundary: the system-model
    rows of the rounds executed since its previous update. Async runs
    additionally carry ``quorum_wait`` — the per-version quorum waits from
    the compiled timeline (arrival of the K-th contribution, BEFORE the
    τ·t_server server floor — deliberately not the commit-to-commit
    duration, which includes that floor and would self-reinforce a τ
    planner): under event-driven commits THAT is the gap adaptive τ
    should fill with server steps, not the max active delay.

    ``telemetry`` carries the TelemetrySink records overlapping the window
    when run_rounds was given a sink — BOTH producers ('sim' and
    'measured'), so a controller chooses its clock (AdaptiveTau's
    ``source=``) instead of being wired to the simulator."""
    start: int
    stop: int
    delays: np.ndarray   # (C, M) simulated client compute times
    masks: np.ndarray    # (C, M) participation·deadline rows consumed
    t_server: float
    t_comm: float
    quorum_wait: Optional[np.ndarray] = None   # (C,) async quorum waits
    telemetry: Tuple[RoundTelemetry, ...] = ()  # sink records for the window


@runtime_checkable
class Controller(Protocol):
    """Chunk-boundary policy hook.

    ``update`` runs once per chunk, before it dispatches, with the window
    of rounds just executed (None at the very first boundary) and the last
    flushed ChunkInfo. The returned dict maps SFLConfig field names to new
    values ('tau', 'deadline', 'lr_server', ...) and is applied via
    dataclasses.replace; unchanged fields may be included (no-ops). A τ
    change re-traces the jit'd round body — the per-algo executable cache
    keyed on (mode, cfg, sfl) amortizes that across chunks, so revisited
    τ values reuse their compiled executables. An optional ``bind(sfl)``
    is called once at run start with the initial config.
    """

    def update(self, round_idx: int, window: Optional[SchedWindow],
               metrics: Optional["ChunkInfo"]) -> Dict[str, Any]: ...


class AdaptiveTau:
    """The paper's "adaptive tuning of τ" (§5) as an engine Controller.

    At each chunk boundary it EMA-smooths the observed straggler gap
    (max active delay per executed round) and re-plans
    τ* = t_straggler / t_server via straggler.plan_tau (Eq. 12). With
    ``couple_lr`` (default) the server lr keeps Thm 4.1's coupling:
    η_s·τ is held at its initial value, so a τ change rescales η_s and
    the per-round server drift stays stable. ``trace`` records the
    (round_idx, τ) decisions for analysis (benchmarks/fig5_adaptive_tau).

    ``source`` picks the clock the straggler gap is observed on:
    'sim' (default) reads the schedule's simulated delays / quorum waits
    from the window rows, the historical behaviour; 'measured' reads the
    measured-clock RoundTelemetry records from ``window.telemetry``
    (block_until_ready-bracketed per-round wall time) and falls back to
    the sim rows when no measured records cover the window — e.g. the
    first boundary, or a run without a sink.
    """

    def __init__(self, tau_max: int = 64, ema: float = 0.5,
                 couple_lr: bool = True, quantize: bool = False,
                 source: str = "sim"):
        if source not in ("sim", "measured"):
            raise ValueError(f"AdaptiveTau source must be 'sim'|'measured', "
                             f"got {source!r}")
        self.tau_max = tau_max
        self.ema = ema
        self.couple_lr = couple_lr
        self.source = source
        self.quantize = quantize      # snap τ to powers of two: bounds the
        self.t_hat: Optional[float] = None        # number of distinct jit
        self._eta_step: Optional[float] = None    # executables (η_s·τ cached
        self.trace: List[Tuple[int, int]] = []    # at bind time)

    def bind(self, sfl) -> None:
        if self.couple_lr and self._eta_step is None:
            self._eta_step = sfl.lr_server * sfl.tau

    # checkpointable controller state (engine saves it in the checkpoint
    # metadata; apply_resume_overrides restores it)
    def state_dict(self) -> Dict[str, Any]:
        return {"t_hat": self.t_hat, "eta_step": self._eta_step}

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        self.t_hat = d.get("t_hat")
        self._eta_step = d.get("eta_step")

    def _observed(self, window) -> np.ndarray:
        if self.source == "measured":
            meas = [r for r in getattr(window, "telemetry", ()) or ()
                    if r.source == "measured"]
            if meas:
                return np.concatenate([np.asarray(r.durations, np.float64)
                                       for r in meas])
        if window.quorum_wait is not None:
            # async window: the observed gap is the quorum wait — how long
            # the server sat idle before the K-th arrival let it commit
            return np.asarray(window.quorum_wait, np.float64)
        act = np.where(window.masks > 0, window.delays, -np.inf)
        per_round = act.max(axis=1)
        return np.where(np.isfinite(per_round), per_round, 0.0)

    def update(self, round_idx, window, metrics):
        if window is None or window.delays.size == 0:
            return {}
        per_round = self._observed(window)
        obs = float(per_round.mean())
        self.t_hat = (obs if self.t_hat is None
                      else self.ema * obs + (1.0 - self.ema) * self.t_hat)
        tau = strag.plan_tau(self.t_hat, window.t_server, self.tau_max)
        if self.quantize:
            tau = min(1 << int(round(np.log2(max(tau, 1)))), self.tau_max)
        self.trace.append((round_idx, tau))
        out = {"tau": tau}
        if self._eta_step is not None:
            out["lr_server"] = self._eta_step / tau
        return out


class AdaptiveQuorum:
    """Graceful-degradation controller: resize the commit quorum K from
    observed fault pressure (core/faults.py).

    At each chunk boundary it reads the window's simulator RoundTelemetry
    records (``SchedWindow.telemetry``) — started dispatches vs.
    contributions lost to crashes, exhausted retries, checksum drops, and
    ring evictions — EMA-smooths the observed delivery rate, and re-plans
    K ≈ ceil(K0 · delivered/started). When a fifth of the fleet's fetches
    die, holding out for the configured K would push every commit into
    the quorum_timeout escape; shrinking K to what the fleet can actually
    fill keeps commits quorum-paced. When delivery recovers the quorum
    grows back toward its configured value. K is clipped to
    [k_min, K0] — never above the initial quorum: the ring geometry (and
    the healthy-state semantics) are sized for K0. ``trace`` records the
    (round_idx, K) decisions, mirroring AdaptiveTau.
    """

    def __init__(self, k_min: int = 1, ema: float = 0.5):
        if k_min < 1:
            raise ValueError(f"AdaptiveQuorum k_min must be >= 1, "
                             f"got {k_min}")
        self.k_min = int(k_min)
        self.ema = ema
        self.k0: Optional[int] = None
        self.rate: Optional[float] = None      # EMA'd delivery rate
        self.trace: List[Tuple[int, int]] = []

    def bind(self, sfl) -> None:
        if self.k0 is None:
            if sfl.quorum <= 0:
                raise ValueError(
                    "AdaptiveQuorum needs a finite initial quorum "
                    "(sfl.quorum > 0): K0 anchors the [k_min, K0] range")
            self.k0 = int(sfl.quorum)

    def state_dict(self) -> Dict[str, Any]:
        return {"k0": self.k0, "rate": self.rate}

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        self.k0 = d.get("k0")
        self.rate = d.get("rate")

    def update(self, round_idx, window, metrics):
        if window is None or self.k0 is None:
            return {}
        recs = [r for r in getattr(window, "telemetry", ()) or ()
                if r.source == "sim"]
        started = sum(r.started for r in recs)
        if not started:                  # no sink, or a zero-fault window
            return {}                    # with no dispatch accounting
        dropped = sum(r.crashed + r.lost + r.corrupt + r.evicted
                      for r in recs)
        obs = max(0.0, 1.0 - dropped / started)
        self.rate = (obs if self.rate is None
                     else self.ema * obs + (1.0 - self.ema) * self.rate)
        k = int(np.clip(int(np.ceil(self.k0 * self.rate)),
                        self.k_min, self.k0))
        self.trace.append((round_idx, k))
        return {"quorum": k}


# ---------------------------------------------------------------------------
# the fused multi-round driver
# ---------------------------------------------------------------------------

class EngineResult(NamedTuple):
    params: Params
    state: State
    metrics: Dict[str, np.ndarray]  # per-round stacks, leading dim = rounds run
    round_loss: np.ndarray          # (rounds,) mask-weighted mean client loss
    round_times: np.ndarray         # (rounds,) simulated per-round wall-clock
    sim_time: float                 # sum(round_times)
    tau_per_round: Optional[np.ndarray] = None  # (rounds,) τ each round;
    #                                 None only when constructed by hand —
    #                                 run_rounds always fills it. Guard
    #                                 before arithmetic all the same.


class ChunkInfo(NamedTuple):
    """Everything a chunk_callback needs about the rounds just flushed —
    engine-computed, so drivers never re-derive losses/times/masks."""
    start: int                      # first absolute round in the chunk
    stop: int                       # one past the last round
    metrics: Dict[str, np.ndarray]  # host-flushed stacks, leading dim C
    masks: np.ndarray               # (C, M) the mask rows the rounds consumed
    round_loss: np.ndarray          # (C,) mask-weighted mean client loss
    round_times: np.ndarray         # (C,) simulated per-round wall-clock


def fold_in_keys(key, start: int, n: int) -> jax.Array:
    """(n, 2) stacked per-round keys: keys[i] = fold_in(key, start + i) —
    identical to what the legacy loops derived one round at a time."""
    return jax.vmap(lambda r: jax.random.fold_in(key, r))(
        jnp.arange(start, start + n))


def make_chunk_fn(algo: Algorithm, cfg: ModelConfig, sfl: SFLConfig):
    """The fused multi-round step: scan algo.round_fn over a chunk of
    precomputed (batches, masks, keys) rows. Shared with the perf-ladder
    cell builder (launch/steps.py train_multi)."""
    def run_chunk(params, state, batches, masks, keys):
        def body(carry, xs):
            p, s = carry
            b, m, k = xs
            p, s, met = algo.round_fn(cfg, sfl, p, s, b, m, k)
            return (p, s), met
        (params, state), mets = jax.lax.scan(body, (params, state),
                                             (batches, masks, keys))
        return params, state, mets
    return run_chunk


def make_async_chunk_fn(algo: Algorithm, cfg: ModelConfig, sfl: SFLConfig):
    """The fused multi-version async step: scan algo.async_round_fn over a
    chunk of precomputed (batches, start_masks, apply_ws, keys) rows from
    the compiled event timeline, carrying (params, record store)."""
    def run_chunk(params, store, batches, start_masks, apply_ws, keys):
        def body(carry, xs):
            p, s = carry
            b, sm, aw, k = xs
            p, s, met = algo.async_round_fn(cfg, sfl, p, s, b, sm, aw, k)
            return (p, s), met
        (params, store), mets = jax.lax.scan(
            body, (params, store), (batches, start_masks, apply_ws, keys))
        return params, store, mets
    return run_chunk


def make_sparse_chunk_fn(algo: Algorithm, cfg: ModelConfig, sfl: SFLConfig):
    """The fused multi-version sparse-async step: scan
    algo.async_sparse_round_fn over the streamed timeline's (C, K) commit-
    batch rows — pre-gathered client batches, start scatter indices into
    the ring store, and apply gather indices + weights — carrying
    (params, ring store)."""
    def run_chunk(params, store, batches, start_client, start_slot,
                  apply_slot, apply_ws, keys):
        def body(carry, xs):
            p, s = carry
            b, sc, ss, asl, aw, k = xs
            p, s, met = algo.async_sparse_round_fn(cfg, sfl, p, s, b, sc,
                                                   ss, asl, aw, k)
            return (p, s), met
        (params, store), mets = jax.lax.scan(
            body, (params, store),
            (batches, start_client, start_slot, apply_slot, apply_ws, keys))
        return params, store, mets
    return run_chunk


def _stack_leaves(*xs):
    # host (numpy) leaves stack on host then upload once; device leaves
    # stack on-device — never bounce device->host->device
    if all(isinstance(x, np.ndarray) for x in xs):
        return jnp.asarray(np.stack(xs))
    return jnp.stack([jnp.asarray(x) for x in xs])


def _stack_chunk(batch_fn, r0: int, n: int):
    """Stack n rounds of per-client batches -> leaves (n, M, ...)."""
    return jax.tree.map(_stack_leaves, *[batch_fn(r0 + i) for i in range(n)])


def _stack_sparse_chunk(batch_fn, r0: int, start_clients: np.ndarray,
                        subset_fn=None, batch_put=None):
    """Stack a sparse chunk's batch rows -> leaves (C, K, ...): per
    version, gather ONLY the starting clients' rows from that round's
    batch (pad rows re-read client 0 — their records land in the ring's
    dropped pad slot, so they are never applied). The device never sees an
    (M, ...) batch, which is what keeps upload volume O(K) per version.

    ``subset_fn(round, client_ids)`` (e.g. FederatedLoader.subset_batch)
    upgrades the gather to O(K) *staging*: only the K starting rows are
    ever materialized on the host — the fleet-width batch is never built.
    Pad rows (-1) clip to client 0, exactly the gather path's convention,
    so both paths are bit-identical. ``batch_put`` (e.g. a NamedSharding
    device_put from launch/fleet.py) places the stacked (C, K, ...) leaves
    before the scan consumes them."""
    rounds = []
    for j in range(start_clients.shape[0]):
        idx = np.clip(start_clients[j], 0, None)
        if subset_fn is not None:
            rounds.append(subset_fn(r0 + j, idx))
        else:
            b = batch_fn(r0 + j)
            rounds.append(jax.tree.map(
                lambda x: x[idx] if isinstance(x, np.ndarray)
                else jnp.take(jnp.asarray(x), jnp.asarray(idx), axis=0), b))
    out = jax.tree.map(_stack_leaves, *rounds)
    return out if batch_put is None else batch_put(out)


def _copy_tree(tree):
    # donation safety: the caller keeps its own params/state buffers
    return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)


def _tree_nbytes(tree) -> int:
    """Bytes staged for a chunk: sum of leaf .nbytes (host or device)."""
    return int(sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree)))


def _cached_jit(algo: Algorithm, mode: str, cfg: ModelConfig, sfl: SFLConfig,
                build: Callable) -> Tuple[Callable, bool]:
    """Per-algorithm-instance jit cache: repeated run_rounds calls with the
    same (algo, cfg, sfl) reuse the compiled executables instead of
    re-tracing a fresh closure every call (jax.jit caches by function
    identity, which a fresh lambda defeats). Returns (fn, built): built is
    True when this call made fn, so its first call traces and compiles."""
    cache = getattr(algo, "_engine_jit_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(algo, "_engine_jit_cache", cache)
    k = (mode, cfg, sfl)
    built = k not in cache
    if built:
        cache[k] = build()
    return cache[k], built


def _has_state(state) -> bool:
    return bool(jax.tree.leaves(state))


def _ckpt_tree(params, state):
    """What the engine checkpoints: params alone for stateless algorithms
    (back-compatible with pre-existing checkpoints), else a
    {'params','state'} bundle so resume is exact for stateful algorithms
    (GAS activation buffer, FedLoRA adapters)."""
    return {"params": params, "state": state} if _has_state(state) else params


def restore_run(checkpointer, algorithm: Union[str, Algorithm],
                cfg: ModelConfig, sfl: SFLConfig, params: Params,
                batch_fn: Callable[[int], Batch], *,
                step: Optional[int] = None,
                **algo_opts) -> Tuple[Params, State, dict]:
    """Restore an engine checkpoint for resume: (params, state, meta).

    Stateful algorithms restore their engine state alongside params when
    the checkpoint carries the {'params','state'} bundle (the state
    template — and hence one batch — is only materialized on that path).
    Legacy params-only checkpoints return state=None: run_rounds then
    re-inits from the first resumed round's batch, the historical
    behaviour. Continue with ``run_rounds(..., state=state,
    start_round=meta['step'] + 1)``; controller-driven runs should also
    pass meta through ``apply_resume_overrides``.
    """
    from repro.ckpt import read_meta
    algo = get_algorithm(algorithm, **algo_opts)
    checkpointer.wait()
    meta = read_meta(checkpointer.dir, step)
    start = meta["step"] + 1
    if meta.get("metadata", {}).get("has_state"):
        state = algo.init_state(cfg, sfl, params,
                                jax.tree.map(jnp.asarray, batch_fn(start)))
        bundle, meta = checkpointer.restore(
            {"params": params, "state": state}, meta["step"])
        return bundle["params"], bundle["state"], meta
    params, meta = checkpointer.restore(params, meta["step"])
    return params, None, meta


def apply_resume_overrides(sfl: SFLConfig, meta: dict,
                           controller: Optional[Controller] = None
                           ) -> SFLConfig:
    """Re-apply a resumed run's controller decisions.

    Engine checkpoints record the SFLConfig fields a controller had
    overridden by save time (metadata['controller_overrides']) and the
    controller's own state (metadata['controller_state'], via its
    state_dict). This replays both onto the resume configuration so the
    run continues at the adapted τ / lrs with the controller's EMA intact
    instead of silently restarting from the CLI values. (The first
    post-resume chunk has no observed window, so it keeps the restored τ;
    a controller that overrode 'deadline' should also rebuild its
    schedule with that deadline.)
    """
    md = meta.get("metadata", {})
    overrides = md.get("controller_overrides") or {}
    if overrides:
        sfl = dataclasses.replace(sfl, **overrides)
    cs = md.get("controller_state")
    if controller is not None and cs and hasattr(controller,
                                                 "load_state_dict"):
        controller.load_state_dict(cs)
    return sfl


def run_rounds(algorithm: Union[str, Algorithm], cfg: ModelConfig,
               sfl: SFLConfig, params: Params, batch_fn: Callable[[int], Batch],
               schedule: strag.Schedule, key, *, rounds: int,
               start_round: int = 0, chunk_size: int = 8,
               mode: str = "scan", state: Optional[State] = None,
               checkpointer=None, ckpt_every: int = 0,
               chunk_callback: Optional[Callable] = None,
               controller: Optional[Controller] = None,
               tau_history: Optional[List[int]] = None,
               quorum_history: Optional[List[int]] = None,
               batch_subset_fn: Optional[Callable] = None,
               batch_put: Optional[Callable] = None,
               telemetry: Optional[TelemetrySink] = None,
               **algo_opts) -> EngineResult:
    """Run rounds [start_round, rounds) of ``algorithm``.

    batch_fn(r) returns the round-r host batch (leaves with leading M dim;
    must be stateless in r so restarts are exact). ``schedule`` provides the
    (R, M) delay/mask rows (cyclic if shorter than the run) and the
    wall-clock knobs. ``key`` is the run's base PRNG key; round r uses
    fold_in(key, r).

    mode='scan': rounds execute in chunks of ``chunk_size`` as one jit'd
    lax.scan per chunk with params/state donated between chunks; metrics
    flush to host (and ``chunk_callback(ChunkInfo, params, state)`` /
    checkpointing fire) only at chunk boundaries, which are aligned to
    ckpt_every. mode='python': the legacy per-round loop — one jit call +
    host sync per round (equivalence/bench baseline); it shares the same
    chunk segmentation so controller decisions land on identical
    boundaries in both modes. mode='async': event-driven semi-async
    (core/events.py) — the schedule is compiled into an arrival-ordered
    timeline, each "round" is one quorum-committed server version
    (sfl.quorum / sfl.staleness_discount are the policy knobs), the
    in-flight record store rides as engine state, and round_times are the
    timeline's commit-to-commit durations; needs an async-capable
    algorithm (async_mu_splitfed). With quorum 0 (= wait for all) and
    discount 1.0 it reproduces mode='scan' exactly.

    sfl.timeline picks the async backend: 'dense' precompiles the whole
    (V, M) timeline up front (small-M reference); 'sparse' streams
    (chunk, k_max) commit batches from the heap DES while the device
    scans the previous chunk, with the in-flight records in a bounded
    arrival-slot ring (events.resolve_store_geometry) — same semantics,
    O(k_max · chunk) host rows instead of O(V · M), and per-version
    batch upload gathered down to the starting clients.

    ``controller`` (e.g. AdaptiveTau) runs at every chunk boundary and may
    override SFLConfig fields for the remaining rounds — 'tau' re-plans the
    unbalanced server updates (re-jit amortized by the per-algo executable
    cache), 'deadline' re-derives the straggler-drop masks from the
    schedule's delay rows. Masks, wall-clock round times, and the τ trace
    (EngineResult.tau_per_round) always reflect what was actually applied.

    ``telemetry`` (a repro.obs TelemetrySink) turns on BOTH producers at
    chunk boundaries: 'sim' records carry the simulator's account of the
    chunk (durations bit-identical to ChunkInfo.round_times, async quorum
    waits, per-cohort arrival latencies) and 'measured' records carry the
    measured clock (block_until_ready-bracketed chunk dispatch, host
    staging seconds/bytes, DES-prefetch overlap). Controllers see the
    window's records via SchedWindow.telemetry. With telemetry=None
    (default) no clock reads or extra syncs happen on the hot path.

    ``sfl.faults`` (a core/faults.py FaultPlan) perturbs the async event
    stream — crash-after-fetch, lossy delivery with up to
    ``sfl.max_retries`` retransmissions, duplication, checksum-dropped
    corruption — and ``sfl.quorum_timeout`` caps how long a commit waits
    for its quorum before proceeding with whatever arrived (weights
    renormalized). None / FaultPlan.none() is bit-exact with the clean
    engine. AdaptiveQuorum (with a telemetry sink) shrinks/grows the
    commit quorum from the observed delivery rate.

    Checkpoints save at step = round index of the last completed round in
    the chunk (stateful algorithms bundle their engine state — see
    restore_run); resume via restore_run and start_round=step+1. Async
    controller runs additionally record the per-version τ / quorum traces
    in the checkpoint metadata ('tau_per_version' / 'quorum_per_version'):
    pass them back as ``tau_history`` / ``quorum_history`` on resume so
    the timeline prefix recompiles with the values that actually executed.
    """
    algo = get_algorithm(algorithm, **algo_opts)
    if mode not in ("scan", "python", "async"):
        raise ValueError(f"run_rounds: mode must be 'scan'|'python'|'async', "
                         f"got {mode!r}")
    if mode == "async" and not hasattr(algo, "async_round_fn"):
        raise ValueError(
            f"mode='async' needs an async-capable algorithm (e.g. "
            f"'async_mu_splitfed'); {algo.name!r} has no async_round_fn")
    if sfl.timeline not in ("dense", "sparse"):
        raise ValueError(f"run_rounds: sfl.timeline must be 'dense'|"
                         f"'sparse', got {sfl.timeline!r}")
    sparse = sfl.timeline == "sparse"
    if sparse and mode != "async":
        raise ValueError(
            "timeline='sparse' is the streaming semi-async path; run it "
            "with mode='async' (the sync modes scan dense schedule rows)")
    if sparse and not hasattr(algo, "async_sparse_round_fn"):
        raise ValueError(f"timeline='sparse' needs an algorithm with "
                         f"async_sparse_round_fn; {algo.name!r} has none")
    if batch_subset_fn is not None and not sparse:
        raise ValueError(
            "batch_subset_fn is the sparse timeline's O(K) staging hook; "
            "the dense modes consume fleet-width batches — set "
            "sfl.timeline='sparse' (with mode='async') to use it")
    if batch_put is not None and not sparse:
        raise ValueError(
            "batch_put places sparse (C, K, ...) staged chunks; it has no "
            "effect outside timeline='sparse'")
    n_run = rounds - start_round
    if n_run <= 0:
        empty = np.zeros((0,), np.float64)
        return EngineResult(params, state, {}, empty, empty, 0.0,
                            np.zeros((0,), np.int64))

    # host work up to the first chunk, closed before the chunk loop (an
    # exception before then leaves it open: run_rounds has failed anyway)
    prepare = span("engine.prepare", start=start_round, stop=rounds)
    prepare.__enter__()
    if state is None:
        # the subset path never materializes a fleet-width batch, not even
        # for the state template: sparse-capable algorithms size their
        # state from sfl (the ring store), so a 1-row probe batch suffices
        batch0 = (batch_subset_fn(start_round, np.zeros(1, np.int64))
                  if batch_subset_fn is not None else batch_fn(start_round))
        state = algo.init_state(cfg, sfl, params,
                                jax.tree.map(jnp.asarray, batch0))

    R = schedule.n_rounds
    cohort_bounds = events._cohort_bounds_of(schedule)
    rows = list(range(start_round, rounds))
    mask_of = getattr(algo, "round_mask",
                      lambda sched, r: sched.masks[r % sched.n_rounds])
    sched_eff = schedule                 # re-derived on controller deadline
    # (n_run, M) mask rows feed sync round_times and controller windows;
    # the sparse path never materializes them — windows rebuild rows on
    # demand from the mask-epoch list below
    time_masks = (None if sparse else
                  np.stack([sched_eff.masks[r % R] for r in rows]))
    timeline: Optional[events.Timeline] = None
    stream: Optional[events.TimelineStream] = None
    qwaits: Optional[np.ndarray] = None
    # fault / degradation counter columns surfaced to RoundTelemetry —
    # sparse fills fcounts from the streamed rows; dense reads the
    # compiled timeline's (V,) columns directly (no ring -> no evictions)
    fault_cols = ("started", "evicted", "crashed", "lost", "corrupt",
                  "dups", "retries", "timeouts")
    fcounts: Optional[np.ndarray] = None
    if mode == "async":
        # compile the semi-async event timeline for the WHOLE run (from
        # version 0, so a resumed run sees the identical prefix and slices
        # its rows); the engine scans its per-version form as data.
        # ``masks`` become the normalized staleness-discounted apply
        # weights — round_loss / ChunkInfo weighting carries over as-is.
        # ``tau_history`` replays a resumed controller run's per-version τ
        # onto the prefix (checkpoint metadata 'tau_per_version'): the DES
        # is only prefix-stable if the prefix is compiled with the τ that
        # actually executed, otherwise the restored record store would
        # meet inconsistent apply weights.
        taus_v = np.full(rounds, sfl.tau, np.int64)
        if tau_history is not None:
            h = np.asarray(tau_history, np.int64)[:rounds]
            taus_v[:len(h)] = h
        # per-version quorum, same replay contract as taus_v: resume must
        # recompile the prefix with the K that actually committed
        # (checkpoint metadata 'quorum_per_version' -> quorum_history)
        quorums_v = np.full(rounds, sfl.quorum, np.int64)
        if quorum_history is not None:
            h = np.asarray(quorum_history, np.int64)[:rounds]
            quorums_v[:len(h)] = h
        if sparse:
            # streaming timeline: no (V, M) rows, no (V, ·) precompute.
            # The DES streams (C, k_max) commit batches chunk-by-chunk;
            # skip(start_round) replays the prefix so the ring/slot state
            # at resume is identical to the original run's. Deadline
            # re-plans append (from_version, schedule) epochs instead of
            # rewriting dense mask rows.
            k_geo, cap_geo = events.resolve_store_geometry(sfl)
            mask_epochs: List[Tuple[int, strag.Schedule]] = [(0, sched_eff)]

            def _mask_row_at(v: int) -> np.ndarray:
                sch = mask_epochs[0][1]
                for v0, cand in mask_epochs:
                    if v >= v0:
                        sch = cand
                return sch.masks[v % R]

            def _new_stream(skip_to: int) -> events.TimelineStream:
                st = events.TimelineStream(
                    sched_eff, rounds, quorum=sfl.quorum,
                    discount=sfl.staleness_discount, taus=taus_v,
                    k_max=k_geo, capacity=cap_geo,
                    mask_row_fn=_mask_row_at, quorums=quorums_v,
                    faults=sfl.faults,
                    quorum_timeout=sfl.quorum_timeout,
                    max_retries=sfl.max_retries)
                st.skip(skip_to)
                return st

            stream = _new_stream(start_round)
            masks = np.zeros((n_run, k_geo), np.float32)
            round_times = np.zeros(n_run, np.float64)
            qwaits = np.zeros(n_run, np.float64)
            fcounts = np.zeros((n_run, len(fault_cols)), np.int64)
        else:
            amask_rows = np.stack([sched_eff.masks[v % R]
                                   for v in range(rounds)])
            with span("engine.compile_timeline", versions=rounds):
                timeline = events.compile_timeline(
                    sched_eff, rounds, quorum=quorums_v,
                    discount=sfl.staleness_discount, tau=taus_v,
                    mask_rows=amask_rows, faults=sfl.faults,
                    quorum_timeout=sfl.quorum_timeout,
                    max_retries=sfl.max_retries)
            masks = timeline.apply_w[start_round:rounds].copy()
            start_masks = timeline.start_mask[start_round:rounds].copy()
            round_times = timeline.durations[start_round:rounds].copy()
    else:
        masks = np.stack([mask_of(sched_eff, r) for r in rows])
        round_times = np.array([algo.time_model(sched_eff.delays[r % R],
                                                time_masks[i], sfl, sched_eff)
                                for i, r in enumerate(rows)])
    tau_used = np.full(n_run, sfl.tau, np.int64)
    keys = fold_in_keys(key, start_round, n_run)

    # chunk segmentation (aligned to ckpt_every) — shared by both modes and
    # by the controller's update boundaries
    segments: List[Tuple[int, int]] = []
    r = start_round
    while r < rounds:
        C = min(chunk_size, rounds - r)
        if ckpt_every:
            C = min(C, ckpt_every - r % ckpt_every)
        segments.append((r, r + C))
        r += C

    if controller is not None and hasattr(controller, "bind"):
        controller.bind(sfl)

    chunks: list = []
    last_info: Optional[ChunkInfo] = None
    applied: Dict[str, Any] = {}    # controller overrides in effect

    def ckpt_meta(**extra):
        md = {"has_state": _has_state(state), **extra}
        if controller is not None:
            if applied:             # values must be JSON-serializable
                md["controller_overrides"] = dict(applied)
            if hasattr(controller, "state_dict"):
                md["controller_state"] = controller.state_dict()
            if mode == "async":
                # per-version τ / K traces: resume must recompile the
                # timeline prefix with the values that actually executed
                # (tau_history / quorum_history)
                md["tau_per_version"] = [int(t) for t in taus_v]
                md["quorum_per_version"] = [int(q) for q in quorums_v]
        return md

    def seg_info(r0, r1):
        i0, i1 = r0 - start_round, r1 - start_round
        seg = chunks[-(r1 - r0):]
        host = {k2: np.concatenate([c[k2] for c in seg]) for k2 in seg[0]}
        m = masks[i0:i1]
        rl = ((host["loss"] * m).sum(1)
              / np.maximum(m.sum(1), 1.0)).astype(np.float64)
        return ChunkInfo(r0, r1, host, m, rl, round_times[i0:i1])

    def _cohort_arrival(r0, r1):
        """Per-cohort mean arrival latency (delay + uplink) of the window's
        active clients — the observed compute/comm ratio input the HASFL
        cut-layer co-planner needs. None on lazy/sparse schedules, which
        never materialize fleet-width rows."""
        if sparse or not hasattr(sched_eff, "delays"):
            return None
        i0, i1 = r0 - start_round, r1 - start_round
        d = np.stack([sched_eff.delays[rr % R] for rr in range(r0, r1)])
        arr = d + events._comm_of(sched_eff)[None, :]
        m = time_masks[i0:i1]
        out = np.zeros(len(cohort_bounds), np.float64)
        for ci, (cs, ce) in enumerate(cohort_bounds):
            w = m[:, cs:ce]
            tot = w.sum()
            out[ci] = float((arr[:, cs:ce] * w).sum() / tot) if tot else 0.0
        return out

    def _sim_emit(r0, r1):
        # the simulator producer: durations are the SAME slice ChunkInfo
        # carries (the bit-consistency gate in tests/test_obs.py), quorum
        # waits the same rows the controller window reads
        i0, i1 = r0 - start_round, r1 - start_round
        counts: Dict[str, int] = {}
        if mode != "async":
            qw = None
        elif sparse:
            qw = qwaits[i0:i1].copy()
            counts = {f: int(fcounts[i0:i1, j].sum())
                      for j, f in enumerate(fault_cols)}
        else:
            qw = timeline.quorum_wait[r0:r1].copy()
            for f in fault_cols:
                col = getattr(timeline, f, None)
                if col is not None:
                    counts[f] = int(col[r0:r1].sum())
        telemetry.emit(RoundTelemetry(
            r0, r1, "sim", mode, round_times[i0:i1].copy(), quorum_wait=qw,
            cohort_arrival=_cohort_arrival(r0, r1), **counts))

    def flush(mets, r0, r1):
        nonlocal last_info
        host = jax.tree.map(np.asarray, mets)      # host sync: chunk boundary
        chunks.append(host)
        i0, i1 = r0 - start_round, r1 - start_round
        m = masks[i0:i1]
        rl = ((host["loss"] * m).sum(1)
              / np.maximum(m.sum(1), 1.0)).astype(np.float64)
        last_info = ChunkInfo(r0, r1, host, m, rl, round_times[i0:i1])
        if telemetry is not None:
            _sim_emit(r0, r1)
        if chunk_callback is not None:
            chunk_callback(last_info, params, state)

    def controller_step(seg_idx):
        """Apply the controller's SFLConfig overrides for rounds >= this
        segment; re-derive masks / wall-clock rows they affect. In async
        mode the future of the event timeline is recompiled — the DES is
        prefix-stable, so the already-executed versions are untouched."""
        nonlocal sfl, sched_eff, timeline, stream, state
        r0 = segments[seg_idx][0]
        window = None
        if seg_idx > 0:
            p0, p1 = segments[seg_idx - 1]
            i0, i1 = p0 - start_round, p1 - start_round
            if sparse:
                wmasks = np.stack([_mask_row_at(rr)
                                   for rr in range(p0, p1)])
                qw = qwaits[i0:i1].copy()
            else:
                wmasks = time_masks[i0:i1]
                qw = (timeline.quorum_wait[p0:p1].copy()
                      if timeline is not None else None)
            window = SchedWindow(
                p0, p1,
                np.stack([sched_eff.delays[rr % R] for rr in range(p0, p1)]),
                wmasks, sched_eff.t_server, sched_eff.t_comm, qw,
                telemetry=(telemetry.window(p0, p1)
                           if telemetry is not None else ()))
        upd = controller.update(r0, window, last_info) or {}
        changed = {k: v for k, v in upd.items() if getattr(sfl, k) != v}
        if not changed:
            return
        if mode == "async" and "staleness_discount" in changed:
            raise ValueError(
                "controllers cannot override staleness_discount mid-run: "
                "already-applied records carry its weights, so the "
                "timeline is not prefix-stable under that change")
        if sparse and "quorum" in changed:
            # the ring geometry was resolved from the INITIAL config and
            # is baked into the store / staged-row shapes; pin the
            # resolved values so the new quorum cannot re-derive a
            # different k_max/capacity under the auto (0) knobs
            if sfl.k_max != k_geo:
                changed["k_max"] = k_geo
            if sfl.ring_capacity != cap_geo:
                changed["ring_capacity"] = cap_geo
        applied.update(changed)
        sfl = dataclasses.replace(sfl, **changed)
        i = r0 - start_round
        if "deadline" in changed:
            nd = np.stack([strag.deadline_mask(sched_eff.delays[j],
                                               sfl.deadline)
                           for j in range(R)])
            sched_eff = dataclasses.replace(
                sched_eff, deadline=nd, masks=sched_eff.participation * nd)
            if sparse:
                # future versions read the re-derived masks through the
                # epoch list; past versions keep the masks they executed
                mask_epochs.append((r0, sched_eff))
            else:
                for j, rr in enumerate(rows[i:], start=i):
                    time_masks[j] = sched_eff.masks[rr % R]
            if mode != "async":
                for j, rr in enumerate(rows[i:], start=i):
                    masks[j] = mask_of(sched_eff, rr)
        if mode == "async":
            if {"tau", "deadline", "quorum"} & set(changed):
                # piecewise knob change: versions >= r0 take the new
                # values, the executed prefix keeps what it ran with
                taus_v[r0:] = sfl.tau
                quorums_v[r0:] = sfl.quorum
                if sparse:
                    # rebuild the stream and replay the (prefix-stable)
                    # DES to r0 — already-flushed rows are untouched and
                    # the ring state at r0 is reproduced exactly
                    stream = _new_stream(r0)
                else:
                    amask_rows[r0:] = np.stack(
                        [sched_eff.masks[v % R]
                         for v in range(r0, rounds)])
                    timeline = events.compile_timeline(
                        sched_eff, rounds, quorum=quorums_v,
                        discount=sfl.staleness_discount, tau=taus_v,
                        mask_rows=amask_rows, faults=sfl.faults,
                        quorum_timeout=sfl.quorum_timeout,
                        max_retries=sfl.max_retries)
                    masks[i:] = timeline.apply_w[r0:rounds]
                    start_masks[i:] = timeline.start_mask[r0:rounds]
                    round_times[i:] = timeline.durations[r0:rounds]
            if "tau" in changed:
                # the record store's τ axis is static per executable
                state = events.resize_store(state, sfl.tau)
        else:
            for j, rr in enumerate(rows[i:], start=i):
                round_times[j] = algo.time_model(sched_eff.delays[rr % R],
                                                 time_masks[j], sfl,
                                                 sched_eff)
        tau_used[i:] = sfl.tau

    if mode != "python":
        params, state = _copy_tree(params), _copy_tree(state)
    prepare.__exit__(None, None, None)

    if mode == "python":
        for si, (r0, r1) in enumerate(segments):
            with span("engine.chunk", start=r0, stop=r1):
                if controller is not None:
                    controller_step(si)
                round_jit, _ = _cached_jit(
                    algo, "python", cfg, sfl,
                    lambda sfl=sfl: jax.jit(
                        lambda p, s, b, m, k: algo.round_fn(
                            cfg, sfl, p, s, b, m, k)))
                t_seg = perf_counter() if telemetry is not None else 0.0
                for rr in range(r0, r1):
                    i = rr - start_round
                    b = jax.tree.map(jnp.asarray, batch_fn(rr))
                    params, state, met = round_jit(
                        params, state, b, jnp.asarray(masks[i]), keys[i])
                    flush(jax.tree.map(lambda a: a[None], met), rr, rr + 1)
                    if (checkpointer is not None and ckpt_every
                            and (rr + 1) % ckpt_every == 0
                            and rr + 1 < rounds):
                        checkpointer.save(rr, _ckpt_tree(params, state),
                                          metadata=ckpt_meta())
                if telemetry is not None:
                    # per-round flush above is the host sync, so the
                    # segment bracket needs no extra block_until_ready
                    dt, C = perf_counter() - t_seg, r1 - r0
                    telemetry.emit(RoundTelemetry(
                        r0, r1, "measured", mode, np.full(C, dt / C),
                        dispatch_seconds=dt))
                if controller is not None and r1 - r0 > 1:
                    # controllers see the whole segment's metrics, exactly
                    # as in scan mode (flush above is per round here)
                    last_info = seg_info(r0, r1)
    else:
        # fused on-device modes: 'scan' over schedule rows, dense 'async'
        # over the compiled timeline's (start_mask, apply_w) rows, sparse
        # 'async' over streamed (C, k_max) commit batches — one loop, the
        # modes differ only in the chunk body and its scanned inputs
        make_fn = (make_sparse_chunk_fn if sparse else
                   make_async_chunk_fn if mode == "async" else make_chunk_fn)
        pending_rows: Optional[events.SparseRows] = None
        tele = telemetry is not None
        for si, (r0, r1) in enumerate(segments):
            with span("engine.chunk", start=r0, stop=r1):
                if controller is not None:
                    controller_step(si)
                chunk_jit, new_program = _cached_jit(
                    algo, mode, cfg, sfl,
                    lambda sfl=sfl: jax.jit(make_fn(algo, cfg, sfl),
                                            donate_argnums=(0, 1)))
                i, C = r0 - start_round, r1 - r0
                # measured-producer bracketing: host staging is [t_host,
                # t_disp), the device chunk is [t_disp, t_sync) closed by
                # block_until_ready — the DES prefetch stays INSIDE that
                # dispatch window (that's the overlap being measured),
                # never after it, so turning telemetry on cannot serialize
                # the host/device pipeline it is measuring. A dispatch with
                # new_program=1 traces and compiles its chunk function.
                t_host = perf_counter() if tele else 0.0
                overlap = 0.0
                if sparse:
                    with span("engine.des_take", start=r0, stop=r1):
                        rows_c = (pending_rows if pending_rows is not None
                                  else stream.take(C))
                    pending_rows = None
                    masks[i:i + C] = rows_c.apply_w
                    round_times[i:i + C] = rows_c.durations
                    qwaits[i:i + C] = rows_c.quorum_wait
                    for j, f in enumerate(fault_cols):
                        fcounts[i:i + C, j] = getattr(rows_c, f)
                    with span("engine.stage", start=r0, stop=r1):
                        staged = _stack_sparse_chunk(
                            batch_fn, r0, rows_c.start_client,
                            subset_fn=batch_subset_fn, batch_put=batch_put)
                    t_disp = perf_counter() if tele else 0.0
                    with span("engine.dispatch", start=r0, stop=r1,
                              new_program=int(new_program)):
                        params, state, mets = chunk_jit(
                            params, state, staged,
                            jnp.asarray(rows_c.start_client),
                            jnp.asarray(rows_c.start_slot),
                            jnp.asarray(rows_c.apply_slot),
                            jnp.asarray(rows_c.apply_w), keys[i:i + C])
                    if controller is None and si + 1 < len(segments):
                        # host/device overlap: JAX dispatch is async, so
                        # the DES generates the NEXT chunk's events while
                        # the device still scans this one (flush below is
                        # the host-sync point). Controller runs can't
                        # prefetch — the next boundary may rebuild the
                        # stream.
                        n0, n1 = segments[si + 1]
                        t_pre = perf_counter() if tele else 0.0
                        with span("engine.des_prefetch", start=n0,
                                  stop=n1):
                            pending_rows = stream.take(n1 - n0)
                        if tele:
                            overlap = perf_counter() - t_pre
                else:
                    with span("engine.stage", start=r0, stop=r1):
                        staged = _stack_chunk(batch_fn, r0, C)
                    extra = ((jnp.asarray(start_masks[i:i + C]),)
                             if mode == "async" else ())
                    t_disp = perf_counter() if tele else 0.0
                    with span("engine.dispatch", start=r0, stop=r1,
                              new_program=int(new_program)):
                        params, state, mets = chunk_jit(
                            params, state, staged, *extra,
                            jnp.asarray(masks[i:i + C]), keys[i:i + C])
                if tele:
                    jax.block_until_ready(mets)
                    t_sync = perf_counter()
                    telemetry.emit(RoundTelemetry(
                        r0, r1, "measured", mode,
                        np.full(C, (t_sync - t_disp) / C),
                        staging_seconds=t_disp - t_host,
                        staging_bytes=_tree_nbytes(staged),
                        dispatch_seconds=t_sync - t_disp,
                        overlap_seconds=overlap))
                with span("engine.flush", start=r0, stop=r1):
                    flush(mets, r0, r1)
                if (checkpointer is not None and ckpt_every
                        and r1 % ckpt_every == 0 and r1 < rounds):
                    checkpointer.save(r1 - 1, _ckpt_tree(params, state),
                                      metadata=ckpt_meta())

    def _cat(k2):
        arrs = [c[k2] for c in chunks]
        shapes = {a.shape[1:] for a in arrs}
        if len(shapes) > 1:     # controller changed τ: pad trailing axes
            full = tuple(max(dims) for dims in zip(*shapes))
            arrs = [np.pad(a, [(0, 0)] + [(0, t - s) for s, t
                                          in zip(a.shape[1:], full)])
                    for a in arrs]
        return np.concatenate(arrs)

    with span("engine.finish", start=start_round, stop=rounds):
        metrics = {k2: _cat(k2) for k2 in chunks[0]}
        loss = metrics["loss"]
        round_loss = ((loss * masks).sum(1)
                      / np.maximum(masks.sum(1), 1.0)).astype(np.float64)
        if checkpointer is not None:
            checkpointer.save(rounds - 1, _ckpt_tree(params, state),
                              metadata=ckpt_meta(loss=float(round_loss[-1])),
                              block=True)
    return EngineResult(params, state, metrics, round_loss,
                        round_times, float(round_times.sum()), tau_used)
