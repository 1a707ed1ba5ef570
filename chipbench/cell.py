"""One cell: set-up, the measured window, the metrics and the check."""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench import check, flops, traffic as traffic_mod, weights

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SCHEDULE_ROUNDS = 64         # the schedule's rows repeat after this
WEIGHTS_STREAM = 0xFFFFFFFF  # fold_in data of the weights key; rounds use r


def load_config(bench: dict, name: str) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return json.loads((ROOT / entry["file"]).read_text())


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         f"peaks.json; add them with their source")
    return table[kind]


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache hits excluded) from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Job:
    """The system under test for one (configuration, traffic, seed): the
    program's config, SFL settings, adapter, schedule (the traffic's
    participation and optional ``schedule`` keys) and feed, and the
    benchmark's own weights and batches."""

    def __init__(self, model_doc: dict, traffic: dict, seed: int):
        import jax
        from repro.configs import SFLConfig, get_config
        from repro.core import engine
        from repro.core import straggler as strag
        self.engine = engine
        self.doc, self.t, self.seed = model_doc, traffic, seed
        self.cfg = get_config(model_doc["arch"]).replace(**model_doc["model"])
        t = traffic
        self.sfl = SFLConfig(
            n_clients=t["clients"], tau=t["tau"],
            n_perturbations=t["perturbations"],
            cut_units=model_doc["cut_units"], lr_server=t["lr_server"],
            lr_client=t["lr_client"], lr_global=t["lr_global"],
            zo_eps=t["zo_eps"], participation=t["participation"],
            perturbation_dist=t["noise"])
        self.algo = engine.get_algorithm(
            t["algorithm"], client_mode=t["client_mode"],
            aggregation=t["aggregation"])
        self.sched = strag.make_schedule(
            seed, SCHEDULE_ROUNDS, t["clients"],
            participation=t["participation"], **t.get("schedule", {}))
        self.key = weights.run_key(seed)
        self.wkey = jax.random.fold_in(self.key, np.uint32(WEIGHTS_STREAM))
        self.layout = weights.program_layout(self.cfg)
        self.make = weights.make_fn(self.layout, self.cfg.tie_embeddings)
        self.norms_halves, self.norms_params = change_norms_fn(
            self.make, model_doc["cut_units"])
        gen = traffic_mod.make_batch_fn(t, self.cfg.vocab_size, seed)

        def staged(r):
            with jax.profiler.TraceAnnotation("chipbench.stage"):
                return gen(r)
        self.batch_fn = gen
        self.staged = staged
        self.C = t["chunk_size"]
        self.params = None

    def _take(self):
        p, self.params = self.params, None
        return p

    def run(self, start: int, stop: int, telemetry=None):
        """Rounds [start, stop) through engine.run_rounds; the job's
        parameters are handed over (donated) and replaced by the result."""
        res = self.engine.run_rounds(
            self.algo, self.cfg, self.sfl, self._take(), self.staged,
            self.sched, self.key, rounds=stop, start_round=start,
            chunk_size=self.C, state=(), telemetry=telemetry)
        self.params = res.params
        return res

    def tokens(self, start: int, stop: int) -> int:
        """Client tokens of the rounds whose update was applied."""
        R = self.sched.n_rounds
        active = sum(int((self.sched.masks[r % R] > 0).sum())
                     for r in range(start, stop))
        return active * self.t["batch"] * self.t["seq"]

    def masks(self, rounds: int) -> np.ndarray:
        """The mask rows rounds [0, rounds) consume."""
        R = self.sched.n_rounds
        return np.stack([self.sched.masks[r % R] for r in range(rounds)])

    def first_call(self) -> Dict:
        """Make the weights and run the first chunk; the program's
        readings for the check."""
        self.params = self.make(self.wkey)
        res = self.run(0, self.C)
        loss = [float(v) for v in res.round_loss]
        client_loss = [float(v) for v in res.metrics["loss"][0]]
        t = time.perf_counter()
        norms = norms_list(self.norms_params(self.params, self.wkey))
        return {"loss": loss, "client_loss": client_loss, "norms": norms,
                "seconds": time.perf_counter() - t}

    def reference(self, precision: str = "f32") -> Dict:
        """The plain reference's readings over the first chunk."""
        from chipbench.reference import Reference
        ref = Reference(self.doc["model"], self.doc["cut_units"], self.t,
                        precision)
        client, server, losses, client_losses = ref.follow(
            self.make(self.wkey), self.key, self.batch_fn,
            self.masks(self.C))
        return {"loss": losses,
                "client_loss": [float(v) for v in client_losses[0]],
                "norms": norms_list(self.norms_halves(client, server,
                                                      self.wkey))}


def change_norms_fn(make, cut: int):
    """Jitted norms of a parameter change from ``make(key)``: one per leaf
    of each half (client, then server; a stacked leaf's layers [0, cut)
    and [cut, L) apart). Returns the function on the two halves and the
    same on the program's whole parameters."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import split

    def norms(client, server, key):
        c0, s0 = split(make(key), cut)
        return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))))
                for half, start in ((client, c0), (server, s0))
                for a, b in zip(jax.tree.leaves(half),
                                jax.tree.leaves(start))]

    return (jax.jit(norms),
            jax.jit(lambda p, key: norms(*split(p, cut), key)))


def norms_list(norms) -> List[float]:
    import jax
    return [float(v) for v in jax.device_get(norms)]


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The metrics of ``kind`` ('end_to_end' | 'per_layer') this cell
    reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def run(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
        device: dict, *, t_start: float):
    """Run one cell of ``bench``; returns (result object, the check's
    lines for the end of standard error). Progress goes to standard error
    as it happens."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return run_docs(bench, cell, load_config(bench, cell["config"]),
                    traffic_mod.load(cell["traffic"]),
                    check.load_limits(cell["name"]), seed, seconds, trace,
                    device, load_peaks(device["kind"]), t_start=t_start)


def run_docs(bench: dict, cell: dict, doc: dict, t: dict,
             limits: Optional[dict], seed: int, seconds: float, trace: bool,
             device: dict, peaks: dict, *, t_start: float):
    """Run a cell given its configuration, traffic and limits."""
    import jax
    from repro import obs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    job = Job(doc, t, seed)
    C = job.C

    # -- set-up: weights, first chunk (compiles), one warm chunk ---------
    t0 = time.perf_counter()
    prog = job.first_call()
    t1 = time.perf_counter()
    t_check = prog.pop("seconds")
    job.run(C, 2 * C)
    jax.block_until_ready(job.params)
    t_warm = time.perf_counter() - t1
    n_chunks = max(1, math.ceil(seconds / t_warm))
    start, stop = 2 * C, 2 * C + n_chunks * C
    # the eager per-run ops whose shapes follow the window's length
    jax.block_until_ready(
        job.engine.fold_in_keys(job.key, start, stop - start)[0:C])
    setup_s = time.perf_counter() - t_start - t_check
    log(f"setup {setup_s:.3f} s (first chunk {t1 - t0:.3f} s, warm "
        f"chunk {t_warm:.3f} s); window {stop - start} rounds")

    # -- the measured window -----------------------------------------------
    sink = obs.TelemetrySink()
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        # host spans come from TraceMe annotations; the Python tracer's
        # per-call events would slow the host loop being measured
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    n_compiles = compiles.n
    tw = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        res = job.run(start, stop, telemetry=sink)
        jax.block_until_ready(job.params)
    window_s = time.perf_counter() - tw
    n_compiles = compiles.n - n_compiles
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))
    losses = np.asarray(res.round_loss, np.float64)
    failed = int(np.sum(~np.isfinite(losses)))
    if failed == 0 and not bool(jax.jit(all_finite)(job.params)):
        failed = 1                  # only the last round's are read
    attempted = stop - start
    job.params = None
    del res
    log(f"window {window_s:.3f} s, {attempted} rounds, "
        f"{n_compiles} compiles inside, losses "
        + " ".join(f"{v:.4f}" for v in losses))
    log("chunks (staging s, dispatch s): " + " ".join(
        f"({r.staging_seconds:.3f}, {r.dispatch_seconds:.3f})"
        for r in sink.records("measured")))

    ctx = {"window_s": window_s, "rounds": attempted, "chips": cell["chips"],
           "tokens": job.tokens(start, stop), "peaks": peaks,
           "flops_per_round": flops.round_flops(doc["model"],
                                                doc["cut_units"], t),
           "replay_bytes_per_round": flops.replay_kernel_bytes(
               job.layout, doc["cut_units"], t),
           "telemetry": sink.records("measured"), "trace": None}
    breakdown = None
    if trace:
        from chipbench import trace_reduce
        try:
            red = trace_reduce.reduce_dir(tdir, "chipbench.window")
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["top_ops"],
                     "idle_gaps": red["top_gaps"]}
        log(f"trace: busy {red['busy_s']:.4f} s of {red['window_s']:.4f} s, "
            f"{red['n_ops']} device ops, {red['n_gaps']} gaps, matmul "
            f"{red['matmul_s']:.4f} s, other {red['other_s']:.4f} s, "
            f"kernels {red['kernel_s']}")

    metrics = {}
    if trace:
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s,
               "tokens_per_s": ctx["tokens"] / window_s}
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # -- the check ----------------------------------------------------------
    tr = time.perf_counter()
    ref = job.reference("f32")
    log(f"reference {time.perf_counter() - tr:.3f} s")
    nums = check.numbers(prog, ref)
    correct, checks = check.verdict(nums, limits)
    log("program loss " + " ".join(f"{v:.6f}" for v in prog["loss"])
        + "; reference loss " + " ".join(f"{v:.6f}" for v in ref["loss"]))
    out = {"correct": bool(correct and failed == 0), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out, check.lines(checks)


def all_finite(tree):
    import jax
    import jax.numpy as jnp
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x))
                              for x in jax.tree.leaves(tree)]))
