"""Training driver: any registered algorithm end to end on real data,
through the unified engine (core/engine.py).

Runs on whatever devices exist: CPU smoke configs locally, the production
mesh on a pod. The per-round Python loop is gone — rounds execute as a
chunked, jit'd lax.scan with donated params/state; straggler delays,
participation/deadline masks, and per-round keys are precomputed host-side
by straggler.make_schedule and scanned as data. Fault tolerance built in:
atomic async checkpoints at chunk boundaries every --ckpt-every rounds,
automatic resume from the latest checkpoint (data order and the schedule
are stateless in the round index, so restarts are exact).

Example (CPU):
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --rounds 20 --tau 2 --clients 4 --batch 2 --seq 64
"""
from __future__ import annotations

import argparse
import os
import signal
import time

import jax
import numpy as np

import repro.obs as obs
from repro.ckpt import Checkpointer
from repro.configs import SFLConfig, get_config
from repro.core import engine, events
from repro.core import straggler as strag
from repro.data import FederatedLoader, SyntheticLM, dirichlet_partition
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, untie_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--algorithm", default="mu_splitfed",
                    choices=sorted(engine.ALGORITHMS))
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2, help="per-client batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--cut", type=int, default=0, help="0 = arch default")
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--straggler-scale", type=float, default=0.0)
    ap.add_argument("--deadline", type=float, default=0.0)
    ap.add_argument("--population", default="",
                    help="heterogeneous fleet spec, e.g. "
                         "'tiered:4x1.0,12x0.2' — per cohort "
                         "<n>x<speed>[@part][~p_drop/p_recover][%%comm_scale]"
                         " (~~p/p: one SHARED chain per cohort — tier-wide "
                         "outages); overrides --clients/--participation (the "
                         "deprecated single-cohort shorthand); "
                         "--straggler-scale becomes the shared jitter")
    ap.add_argument("--async", dest="run_async", action="store_true",
                    help="event-driven semi-async execution (core/events.py)"
                         ": commit a server version as soon as --quorum "
                         "contributions arrive; late arrivals fold into a "
                         "later commit, discounted by --staleness-discount "
                         "per missed commit. Implies "
                         "--algorithm async_mu_splitfed")
    ap.add_argument("--quorum", type=int, default=0,
                    help="semi-async commit quorum K (0 = wait for all "
                         "pending contributions — the synchronous barrier)")
    ap.add_argument("--staleness-discount", type=float, default=1.0,
                    help="weight base for stale contributions: a record "
                         "applied s commits after its fetch weighs "
                         "discount**s before per-commit normalization")
    ap.add_argument("--timeline", default="dense",
                    choices=["dense", "sparse"],
                    help="async timeline backend: 'dense' precompiles "
                         "(V, M) rows (small-M reference); 'sparse' "
                         "streams (chunk, k_max) commit batches over an "
                         "arrival-slot ring store — pick it for large "
                         "fleets (quorum K << M)")
    ap.add_argument("--k-max", type=int, default=0,
                    help="sparse timeline: per-version commit-batch width "
                         "(0 = auto: 4x quorum, floor 16, capped at M)")
    ap.add_argument("--ring-capacity", type=int, default=0,
                    help="sparse timeline: in-flight record slots (0 = "
                         "auto: an 8-batch staleness window, capped at M)")
    ap.add_argument("--loader", default="fleet",
                    choices=["fleet", "subset"],
                    help="sparse data staging: 'fleet' gathers each "
                         "version's rows from a fleet-width (M, ...) stack; "
                         "'subset' materializes only the <= k_max clients "
                         "that start each version (O(K) host staging, "
                         "bit-exact vs the gather) — requires --timeline "
                         "sparse")
    ap.add_argument("--fleet-shard", type=int, default=0,
                    help="shard the arrival-slot ring store, fleet system "
                         "vectors, and staged commit batches over N devices "
                         "on a ('data',) mesh (launch/fleet.py; 0 = off, "
                         "replicated). Requires --async --timeline sparse "
                         "and ring/k_max geometry divisible by N")
    ap.add_argument("--faults", default="",
                    help="fault-injection plan (core/faults.py), e.g. "
                         "'crash=0.1,loss=0.05,dup=0.02,corrupt=0.01,"
                         "kill=40' — crash-after-fetch / delivery-loss / "
                         "duplication / corruption rates per dispatch, "
                         "'key@cohort=rate' per-cohort overrides, "
                         "'backoff=s' crash re-dispatch base, 'kill=R' "
                         "SIGKILLs the process after the chunk containing "
                         "round R (checkpoint-resume exercise). Event "
                         "rates require --async")
    ap.add_argument("--quorum-timeout", type=float, default=0.0,
                    help="graceful degradation: commit with however many "
                         "contributions arrived once the quorum has "
                         "waited this long (weights renormalized; 0 = "
                         "wait forever). Requires --async")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="retransmissions per lost delivery before the "
                         "contribution is dropped")
    ap.add_argument("--adaptive-quorum", action="store_true",
                    help="shrink/grow the commit quorum K from the "
                         "observed delivery rate (engine.AdaptiveQuorum; "
                         "--quorum is K0, the cap). Requires --async and "
                         "a --quorum > 0")
    ap.add_argument("--adaptive-tau", action="store_true",
                    help="re-plan tau at chunk boundaries from the observed "
                         "straggler gap (engine.AdaptiveTau; --tau is the "
                         "starting point)")
    ap.add_argument("--tau-max", type=int, default=64,
                    help="cap for --adaptive-tau's planner")
    ap.add_argument("--tau-source", default="sim",
                    choices=["sim", "measured"],
                    help="clock --adaptive-tau observes the straggler gap "
                         "on: 'sim' reads the schedule's simulated rows "
                         "(historical behaviour); 'measured' reads the "
                         "measured-clock RoundTelemetry records from the "
                         "engine's sink (real per-chunk wall time)")
    ap.add_argument("--telemetry", action="store_true",
                    help="attach a TelemetrySink to the engine (sim + "
                         "measured producers at chunk boundaries) and print "
                         "the telemetry/metrics summary at run end")
    ap.add_argument("--trace-out", default="",
                    help="write the span trace here at run end: .json = "
                         "Chrome trace-event format (chrome://tracing / "
                         "perfetto), .jsonl = one span per line")
    ap.add_argument("--log-jsonl", default="",
                    help="structured JSONL run log: per-round rows plus "
                         "per-chunk RoundTelemetry summaries; resume "
                         "truncates re-run rounds so nothing duplicates")
    ap.add_argument("--log-every", type=int, default=1,
                    help="log every Nth round row to --log-jsonl (chunk "
                         "rows always log)")
    ap.add_argument("--t-server", type=float, default=0.1,
                    help="simulated server step time (s) for the wall-clock "
                         "model")
    ap.add_argument("--t-gen", type=float, default=0.0,
                    help="GAS activation-generation overhead (s) per round")
    ap.add_argument("--t-comm", type=float, default=0.0,
                    help="simulated per-round communication time (s), "
                         "charged by every algorithm's wall-clock model")
    ap.add_argument("--aggregation", default=None,
                    choices=["dense", "seed_replay"],
                    help="server aggregation (default dense; --async "
                         "requires seed_replay — the record store is the "
                         "replay wire format)")
    ap.add_argument("--client-mode", default="parallel",
                    choices=["parallel", "sequential"])
    ap.add_argument("--loop", default=None, choices=["scan", "python"],
                    help="fused multi-round scan (default) or the legacy "
                         "one-dispatch-per-round loop; incompatible with "
                         "--async (which runs the event-driven mode)")
    ap.add_argument("--chunk-size", type=int, default=8,
                    help="rounds fused per scan dispatch")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr-server", type=float, default=1e-3)
    ap.add_argument("--lr-client", type=float, default=5e-4)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.run_async:
        if args.loop is not None:
            raise SystemExit("--async and --loop are mutually exclusive: "
                             "--async runs the event-driven mode")
        if args.algorithm == "mu_splitfed":
            args.algorithm = "async_mu_splitfed"
        elif args.algorithm != "async_mu_splitfed":
            raise SystemExit(f"--async supports async_mu_splitfed, "
                             f"not {args.algorithm}")
        if args.aggregation == "dense":
            raise SystemExit("--async requires --aggregation seed_replay: "
                             "the in-flight record store is the seed-replay "
                             "wire format")
        args.aggregation = "seed_replay"
        args.loop = "async"
    else:
        if args.quorum or args.staleness_discount != 1.0:
            raise SystemExit("--quorum/--staleness-discount only take "
                             "effect under --async (the synchronous modes "
                             "never read them)")
        if args.timeline != "dense":
            ap.error("--timeline sparse is the semi-async streaming "
                     "backend; it requires --async")
        if args.loop is None:
            args.loop = "scan"
        if args.aggregation is None:
            args.aggregation = "dense"
    fault_plan = None
    if args.faults:
        from repro.core.faults import parse_faults
        try:
            fault_plan = parse_faults(args.faults)
        except ValueError as e:
            ap.error(str(e))
    if not args.run_async:
        if fault_plan is not None and fault_plan.any():
            ap.error("--faults event rates perturb the semi-async event "
                     "stream; they require --async (kill=R alone works "
                     "in any mode)")
        if args.quorum_timeout or args.adaptive_quorum:
            ap.error("--quorum-timeout/--adaptive-quorum are semi-async "
                     "degradation knobs; they require --async")
    if args.quorum_timeout < 0:
        ap.error(f"--quorum-timeout must be >= 0: got "
                 f"{args.quorum_timeout}")
    if args.max_retries < 0:
        ap.error(f"--max-retries must be >= 0: got {args.max_retries}")
    if args.adaptive_quorum and args.quorum <= 0:
        ap.error("--adaptive-quorum plans within [1, K0]; pass a finite "
                 "initial --quorum > 0")
    if args.adaptive_quorum and args.adaptive_tau:
        ap.error("--adaptive-tau and --adaptive-quorum are separate "
                 "controllers; the engine runs one controller per run")
    if args.loader == "subset" and args.timeline != "sparse":
        ap.error("--loader subset is the sparse O(K) staging path; it "
                 "requires --async --timeline sparse")
    if args.fleet_shard < 0:
        ap.error(f"--fleet-shard must be >= 0 (0 = off): got "
                 f"{args.fleet_shard}")
    if args.fleet_shard and args.timeline != "sparse":
        ap.error("--fleet-shard places the sparse ring store; it requires "
                 "--async --timeline sparse")

    cfg = get_config(args.arch, smoke=args.smoke)
    # the client fleet: an explicit heterogeneous population, or the
    # deprecated scalar shorthand resolved to a single cohort
    population = (strag.parse_population(
        args.population, straggler_scale=args.straggler_scale)
        if args.population else None)
    n_clients = population.n_clients if population else args.clients
    # validate the semi-async policy knobs against the RESOLVED fleet size
    # (an oversized quorum used to be silently clamped inside the DES)
    if args.quorum < 0 or args.quorum > n_clients:
        ap.error(f"--quorum must be in [0, n_clients]: got {args.quorum} "
                 f"with n_clients={n_clients} (0 = wait for all pending)")
    if not 0.0 <= args.staleness_discount <= 1.0:
        ap.error(f"--staleness-discount must be in [0.0, 1.0]: got "
                 f"{args.staleness_discount} (weight base per missed "
                 f"commit)")
    if args.k_max < 0 or args.ring_capacity < 0:
        ap.error("--k-max/--ring-capacity must be >= 0 (0 = auto)")
    if population is not None:
        print(f"population: {population.describe()}  (M={n_clients})")
    sfl = SFLConfig(n_clients=n_clients, tau=args.tau,
                    cut_units=args.cut or cfg.default_cut_units,
                    lr_server=args.lr_server, lr_client=args.lr_client,
                    participation=args.participation,
                    straggler_rate=args.straggler_scale,
                    deadline=args.deadline, population=population,
                    quorum=args.quorum,
                    staleness_discount=args.staleness_discount,
                    timeline=args.timeline, k_max=args.k_max,
                    ring_capacity=args.ring_capacity,
                    faults=fault_plan, quorum_timeout=args.quorum_timeout,
                    max_retries=args.max_retries)
    if fault_plan is not None:
        print(f"faults: {fault_plan.describe()}"
              + (f"  quorum_timeout={args.quorum_timeout:g}"
                 if args.quorum_timeout else "")
              + f"  max_retries={args.max_retries}")
    # resolve the mesh placement BEFORE any device work: geometry errors
    # (ring/k_max not divisible by the 'data' axis, too few devices) are
    # launch-time misconfigurations, not mid-run surprises
    placement = None
    if args.fleet_shard:
        if args.fleet_shard > len(jax.devices()):
            ap.error(f"--fleet-shard {args.fleet_shard} exceeds the "
                     f"{len(jax.devices())} available devices")
        from repro.launch.fleet import build_fleet_placement
        try:
            placement = build_fleet_placement(
                sfl, data_devices=args.fleet_shard)
        except ValueError as e:
            ap.error(str(e))
        print(f"fleet placement: ring store sharded over "
              f"{args.fleet_shard} devices ({placement.plan})")
    key = jax.random.PRNGKey(args.seed)
    params = untie_params(cfg, init_params(cfg, key))

    # data: synthetic LM, Dirichlet-partitioned across clients
    n_samples = 4096
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                     seed=args.seed)
    pseudo_labels = np.arange(n_samples) % 10
    parts = dirichlet_partition(pseudo_labels, n_clients, alpha=0.5,
                                seed=args.seed)
    loader = FederatedLoader(ds, parts, args.batch, seed=args.seed)

    algo = engine.get_algorithm(args.algorithm, **(
        {"client_mode": args.client_mode, "aggregation": args.aggregation}
        if args.algorithm in ("mu_splitfed", "vanilla", "async_mu_splitfed")
        else {"aggregation": args.aggregation}
        if args.algorithm == "gas" else {}))
    if args.run_async:
        print(f"semi-async: quorum {args.quorum or 'all'} of {n_clients}, "
              f"staleness discount {args.staleness_discount}, "
              f"timeline {args.timeline}" + (
                  " (k_max {}, ring {})".format(
                      *events.resolve_store_geometry(sfl))
                  if args.timeline == "sparse" else ""))

    if args.log_every < 1:
        ap.error(f"--log-every must be >= 1: got {args.log_every}")
    if args.tau_source == "measured" and not args.adaptive_tau:
        ap.error("--tau-source measured configures --adaptive-tau's clock; "
                 "pass --adaptive-tau")
    controller = (engine.AdaptiveTau(tau_max=args.tau_max,
                                     source=args.tau_source)
                  if args.adaptive_tau
                  else engine.AdaptiveQuorum()
                  if args.adaptive_quorum else None)
    # the observability layer: sink (engine producers -> controller/log),
    # tracer (span records over the hot path), metrics (running totals).
    # AdaptiveQuorum observes fault counters through the sink, so it
    # forces one on.
    sink = (obs.TelemetrySink()
            if (args.telemetry or args.log_jsonl or args.adaptive_quorum
                or args.tau_source == "measured") else None)
    tracer = None
    if args.trace_out:
        tracer = obs.SpanTracer()
        obs.install(tracer)
    registry = obs.get_registry()

    # fault tolerance: resume if a checkpoint exists (engine state —
    # e.g. the GAS activation buffer — rides along in the bundle, and
    # controller decisions/EMA state replay from the metadata)
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start_round, state = 0, None
    tau_history, quorum_history = None, None
    if ck is not None:
        from repro.ckpt import latest_good_step, read_meta
        if latest_good_step(args.ckpt_dir) is not None:
            # replay controller overrides BEFORE restoring: stateful
            # templates (e.g. the async record store's τ axis) are built
            # from the adapted config. latest_good_step walks past any
            # checkpoint that fails its content checksum — a crash mid-
            # save resumes from the last good chunk boundary.
            sfl = engine.apply_resume_overrides(
                sfl, read_meta(args.ckpt_dir), controller)
            params, state, meta = engine.restore_run(
                ck, algo, cfg, sfl, params, loader.round_batch)
            start_round = meta["step"] + 1
            # async controller runs: recompile the timeline prefix with
            # the per-version τ / quorum that actually executed
            tau_history = meta["metadata"].get("tau_per_version")
            quorum_history = meta["metadata"].get("quorum_per_version")
            print(f"[resume] from round {start_round} (tau={sfl.tau})")

    # the whole system model — per-cohort delays, availability chains,
    # participation, deadline drops — as precomputed (R, M) data the
    # engine scans
    sched = strag.make_schedule(
        args.seed, args.rounds, population=strag.ClientPopulation.resolve(sfl),
        deadline=args.deadline,
        t_server=args.t_server, t_gen=args.t_gen, t_comm=args.t_comm)

    runlog = (obs.RunLog(args.log_jsonl, resume_round=start_round,
                         log_every=args.log_every)
              if args.log_jsonl else None)

    wall = strag.WallClock()
    t0 = time.time()

    def on_chunk(info, p, s):
        for i, r in enumerate(range(info.start, info.stop)):
            sim_t = wall.tick(info.round_times[i])
            print(f"round {r:4d}  loss {info.round_loss[i]:.4f}  active "
                  f"{int((info.masks[i] > 0).sum())}/{n_clients}  "
                  f"wall {time.time()-t0:.1f}s  sim_t {sim_t:.1f}")
            if runlog is not None:
                runlog.round(r, loss=float(info.round_loss[i]),
                             active=int((info.masks[i] > 0).sum()),
                             sim_t=float(sim_t),
                             wall_s=round(time.time() - t0, 3))
        if sink is not None:
            registry.counter("train.rounds").inc(info.stop - info.start)
            registry.counter("train.chunks").inc()
            registry.gauge("train.last_loss").set(float(info.round_loss[-1]))
            h = registry.histogram("train.sim_round_seconds")
            for dt in info.round_times:
                h.observe(float(dt))
            meas = sink.latest("measured")
            if meas is not None and meas.stop == info.stop:
                registry.histogram("train.chunk_dispatch_seconds").observe(
                    meas.dispatch_seconds)
                registry.counter("train.staging_bytes").inc(
                    meas.staging_bytes)
        if sink is not None:
            # degradation accounting: mirror the chunk's simulator fault
            # counters into the metrics registry so /stats surfaces
            # contribution loss without replaying the telemetry ring
            for rec in sink.window(info.start, info.stop, "sim"):
                for f in ("started", "evicted", "crashed", "lost",
                          "corrupt", "dups", "retries", "timeouts"):
                    n = getattr(rec, f)
                    if n:
                        registry.counter(f"train.faults.{f}").inc(n)
        if runlog is not None:
            runlog.chunk(info.start, info.stop,
                         telemetry=(sink.window(info.start, info.stop)
                                    if sink is not None else ()))
        if (fault_plan is not None
                and info.start <= fault_plan.kill_round < info.stop):
            # the host-kill schedule: SIGKILL (no cleanup, no atexit —
            # the real failure mode) right after the chunk containing
            # kill_round flushed and BEFORE its checkpoint lands; resume
            # restarts from the previous good boundary
            print(f"[faults] kill={fault_plan.kill_round}: SIGKILL after "
                  f"chunk [{info.start}, {info.stop})", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)

    if placement is not None and state is None:
        # pre-place the initial ring store so the scan's donated state
        # carries the 'data'-axis layout from version 0
        state = placement.place_store(events.init_store(sfl))
    result = engine.run_rounds(
        algo, cfg, sfl, params, loader.round_batch, sched, key,
        rounds=args.rounds, start_round=start_round, state=state,
        chunk_size=args.chunk_size, mode=args.loop, checkpointer=ck,
        ckpt_every=args.ckpt_every, chunk_callback=on_chunk,
        controller=controller, tau_history=tau_history,
        quorum_history=quorum_history,
        batch_subset_fn=(loader.subset_batch
                         if args.loader == "subset" else None),
        batch_put=placement.batch_put if placement is not None else None,
        telemetry=sink)
    if placement is not None:
        # where the donated ring store ended up after the last chunk
        devices = {d for x in jax.tree.leaves(result.state)
                   for d in x.sharding.device_set}
        specs = {k: str(v.sharding.spec) for k, v in result.state.items()}
        print(f"fleet placement: final ring store on {len(devices)} "
              f"devices {specs}")
    if controller is not None and controller.trace:
        vals = [t for _, t in controller.trace]
        if args.adaptive_quorum:
            print(f"adaptive quorum: K0 {args.quorum} -> final {vals[-1]} "
                  f"(decisions: {vals})")
        else:
            print(f"adaptive tau ({args.tau_source}): start {args.tau} -> "
                  f"final {vals[-1]} (decisions: {vals})")
    if runlog is not None:
        runlog.close()
        print(f"run log: {args.log_jsonl}")
    if tracer is not None:
        n_spans = (tracer.export_jsonl(args.trace_out)
                   if args.trace_out.endswith(".jsonl")
                   else tracer.export_chrome(args.trace_out))
        print(f"trace: {n_spans} spans -> {args.trace_out}")
    if args.telemetry:
        import json
        print("telemetry summary:")
        print(json.dumps(sink.summary(), indent=2, sort_keys=True))
        print("metrics:")
        print(json.dumps(registry.snapshot(), indent=2, sort_keys=True))
    return result.params


if __name__ == "__main__":
    main()
