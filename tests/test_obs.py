"""Observability layer (repro.obs): span tracer nesting/export and its
zero-cost-when-disabled contract, TelemetrySink ring/window semantics,
metrics registry, run-log resume truncation, the measure() helper — and
the engine integration gates: the sim telemetry producer is bit-identical
to ChunkInfo-derived values (sync AND async), the measured producer
brackets every chunk, telemetry survives controller re-plans and
checkpoint resume."""
import glob
import itertools
import json
import os
import tempfile
import threading
import tracemalloc

import jax
import numpy as np
import pytest

from conftest import tiny_lm_cfg
from repro.ckpt import Checkpointer, latest_step
from repro.configs import SFLConfig
from repro.core import engine
from repro.core import straggler as strag
from repro.core.population import ClientPopulation, Cohort, DelayModel
from repro.models import init_params, untie_params
from repro.obs import (Measurement, RoundTelemetry, RunLog, SpanTracer,
                       TelemetrySink, get_registry, install, measure,
                       read_jsonl, span)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import _NULL_SPAN, get_tracer

M = 4
ROUNDS = 8


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_export_roundtrip(tmp_path):
    """Nested spans record depth and containment; both export formats
    round-trip every record."""
    tr = SpanTracer()
    prev = install(tr)
    try:
        with span("outer", k=1):
            with span("inner"):
                pass
            with span("inner2") as s:
                s.set(rounds=8)
    finally:
        install(prev)
    recs = {r.name: r for r in tr.records()}
    assert set(recs) == {"outer", "inner", "inner2"}
    assert recs["outer"].depth == 0
    assert recs["inner"].depth == recs["inner2"].depth == 1
    # children complete inside the parent window
    for child in ("inner", "inner2"):
        assert recs[child].start >= recs["outer"].start
        assert (recs[child].start + recs[child].duration
                <= recs["outer"].start + recs["outer"].duration + 1e-9)
    assert recs["outer"].attrs == {"k": 1}
    assert recs["inner2"].attrs == {"rounds": 8}

    jl = tmp_path / "t.jsonl"
    assert tr.export_jsonl(str(jl)) == 3
    rows = [json.loads(l) for l in jl.read_text().splitlines()]
    assert {r["name"] for r in rows} == {"outer", "inner", "inner2"}

    ct = tmp_path / "t.json"
    assert tr.export_chrome(str(ct)) == 3
    events = json.loads(ct.read_text())["traceEvents"]
    assert all(e["ph"] == "X" for e in events)
    assert {e["name"] for e in events} == {"outer", "inner", "inner2"}


def test_no_tracer_means_null_span():
    """With no installed tracer and no profiler collecting the probe
    returns ONE shared null object — no allocation, no clock read, nothing
    recorded."""
    prev = install(None)
    try:
        assert not jax.profiler.TraceAnnotation.is_enabled()
        s1, s2 = span("a", x=1), span("b")
        assert s1 is s2 is _NULL_SPAN
        with s1 as s:
            s.set(anything=0)        # no-op, must not raise
        tracemalloc.start()
        try:
            for _ in range(2):       # the second pass is the one read
                before = tracemalloc.get_traced_memory()[0]
                for _ in itertools.repeat(None, 1000):
                    with span("engine.stage", start=0, stop=2):
                        pass
                grown = tracemalloc.get_traced_memory()[0] - before
        finally:
            tracemalloc.stop()
        assert grown == 0
    finally:
        install(prev)


def _host_events(trace_dir, prefix):
    """(name, start_ns, end_ns, stats) of the host events of a profiler
    trace whose names start with ``prefix``, in time order, read through
    ProfileData as chipbench/trace_reduce.py reads them."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return sorted((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                   {k: v for k, v in e.stats})
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith(prefix))


def test_span_writes_a_profiler_annotation(tmp_path):
    """While the profiler collects, a span also lands in its trace with
    its attributes (those set mid-span too), and still records into an
    installed tracer."""
    tr = SpanTracer()
    prev = install(tr)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("outer", k=1) as s:
            s.set(n=2)
    finally:
        jax.profiler.stop_trace()
        install(prev)
    assert [(r.name, r.attrs) for r in tr.records()] == [
        ("outer", {"k": 1, "n": 2})]
    (ev,) = _host_events(str(tmp_path), "outer")
    assert ev[3] == {"k": 1, "n": 2}


def test_disabled_tracer_is_null_and_records_nothing():
    tr = SpanTracer(enabled=False)
    prev = install(tr)
    try:
        assert span("hot") is _NULL_SPAN
        with span("hot"):
            pass
    finally:
        install(prev)
    assert tr.records() == []


def test_install_returns_previous():
    tr = SpanTracer()
    prev = install(tr)
    try:
        assert get_tracer() is tr
    finally:
        assert install(prev) is tr


def test_tracer_thread_safety():
    tr = SpanTracer()
    prev = install(tr)

    def work(i):
        for _ in range(50):
            with span("w", tid=i):
                pass
    try:
        ts = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
    finally:
        install(prev)
    assert len(tr.records()) == 200
    # depth is per-thread: no cross-thread nesting bleed
    assert {r.depth for r in tr.records()} == {0}


# ---------------------------------------------------------------------------
# telemetry sink
# ---------------------------------------------------------------------------

def _rec(start, stop, source="sim", **kw):
    return RoundTelemetry(start, stop, source, "scan",
                          np.arange(stop - start, dtype=np.float64), **kw)


def test_sink_ring_window_latest():
    sink = TelemetrySink(capacity=3)
    for i in range(5):
        sink.emit(_rec(i * 2, i * 2 + 2))
    assert sink.emitted == 5
    assert len(sink.records()) == 3            # ring dropped the oldest 2
    assert sink.records()[0].start == 4
    # window query: overlap semantics, half-open
    w = sink.window(5, 7)
    assert [(r.start, r.stop) for r in w] == [(4, 6), (6, 8)]
    assert sink.window(100, 200) == ()
    assert sink.latest().start == 8
    assert sink.latest("measured") is None
    sink.clear()
    assert sink.records() == [] and sink.emitted == 5


def test_sink_rejects_bad_capacity():
    with pytest.raises(ValueError):
        TelemetrySink(capacity=0)


def test_sink_summary_and_t_wall_stamp():
    sink = TelemetrySink()
    sink.emit(_rec(0, 4))
    sink.emit(_rec(0, 4, source="measured", dispatch_seconds=0.5,
                   staging_seconds=0.1, staging_bytes=1024))
    s = sink.summary()
    assert s["emitted"] == 2 and set(s["sources"]) == {"sim", "measured"}
    assert s["sources"]["measured"]["staging_bytes"] == 1024
    assert s["sources"]["sim"]["rounds"] == 4
    assert all(r.t_wall > 0 for r in sink.records())   # stamped on emit


def test_round_telemetry_json():
    r = _rec(2, 5, quorum_wait=np.array([1.0, 2.0, 3.0]))
    j = r.to_json()
    assert j["start"] == 2 and j["stop"] == 5
    assert j["durations"] == [0.0, 1.0, 2.0]
    assert j["quorum_wait"] == [1.0, 2.0, 3.0]
    assert j["cohort_arrival"] is None
    json.dumps(j)                               # fully serializable
    assert r.n_rounds == 3


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    h = reg.histogram("h")
    for v in (0.001, 0.01, 0.01, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["c"]["value"] == 5
    assert snap["g"]["value"] == 2.5
    assert snap["h"]["count"] == 4
    assert snap["h"]["min"] == 0.001 and snap["h"]["max"] == 5.0
    # quantile estimate is a bucket upper bound >= the true value
    assert h.quantile(0.5) >= 0.01
    with pytest.raises(TypeError):
        reg.gauge("c")                          # kind collision
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    assert get_registry() is get_registry()     # process-wide singleton


# ---------------------------------------------------------------------------
# run log
# ---------------------------------------------------------------------------

def test_runlog_write_resume_and_log_every(tmp_path):
    p = str(tmp_path / "run.jsonl")
    with RunLog(p, log_every=2) as log:
        for r in range(6):
            log.round(r, loss=float(r))
        log.chunk(0, 4, telemetry=(_rec(0, 4),), extra=1)
        log.chunk(4, 8, telemetry=())
    rounds = read_jsonl(p, kind="round")
    assert [r["round"] for r in rounds] == [0, 2, 4]   # log_every=2
    chunks = read_jsonl(p, kind="chunk")
    assert len(chunks) == 2
    assert chunks[0]["telemetry"][0]["durations"] == [0.0, 1.0, 2.0, 3.0]

    # resume at round 4: round rows >= 4 and chunks reaching past 4 drop
    with RunLog(p, resume_round=4) as log:
        log.round(4, loss=9.0)
    rows = read_jsonl(p)
    kinds = [(r["kind"], r.get("round", r.get("start"))) for r in rows]
    assert kinds == [("round", 0), ("round", 2), ("chunk", 0), ("round", 4)]


def test_read_jsonl_tolerates_partial_tail(tmp_path):
    p = tmp_path / "r.jsonl"
    p.write_text('{"kind": "round", "round": 0}\n{"kind": "rou')
    assert len(read_jsonl(str(p))) == 1


# ---------------------------------------------------------------------------
# measure helper
# ---------------------------------------------------------------------------

def test_measure_returns_triple():
    m = measure(lambda n: bytes(n), 1 << 20)
    assert isinstance(m, Measurement)
    assert len(m.result) == 1 << 20
    assert m.seconds > 0
    assert m.peak_bytes >= 1 << 20


def test_measure_exception_safe():
    """A raising body must still stop tracemalloc (bench_timeline's
    refuse-dense path raises SystemExit inside measure)."""
    with pytest.raises(SystemExit):
        measure(lambda: (_ for _ in ()).throw(SystemExit(2)))
    assert not tracemalloc.is_tracing()


# ---------------------------------------------------------------------------
# engine integration: the two producers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = tiny_lm_cfg(dtype="float32")
    key = jax.random.PRNGKey(0)
    params = untie_params(cfg, init_params(cfg, key))
    sfl = SFLConfig(n_clients=M, tau=2, cut_units=1, lr_server=5e-3,
                    lr_client=1e-3, lr_global=1.0)
    sched = strag.make_schedule(0, ROUNDS, M, straggler_scale=2.0,
                                participation=0.5, t_server=0.1, t_comm=0.2)

    def batch_fn(r):
        k = jax.random.fold_in(jax.random.PRNGKey(99), r)
        t = jax.random.randint(k, (M, 2, 16), 0, cfg.vocab_size)
        return {"tokens": t, "labels": t}

    return cfg, params, sfl, sched, batch_fn, key


def _async_sfl(timeline="sparse"):
    pop = ClientPopulation(cohorts=(
        Cohort(name="fast", n=3, delay=DelayModel(base=0.3, scale=0.0)),
        Cohort(name="slow", n=1, delay=DelayModel(base=4.0, scale=0.0)),
    ))
    return SFLConfig(n_clients=M, tau=2, cut_units=1, lr_server=5e-3,
                     lr_client=1e-3, lr_global=1.0, population=pop,
                     quorum=2, staleness_discount=0.5, timeline=timeline)


def _run_with_sink(cfg, sfl, params, batch_fn, sched, key, *, mode,
                   algorithm="mu_splitfed", rounds=ROUNDS, chunk=3, **kw):
    sink = TelemetrySink()
    infos = []
    res = engine.run_rounds(algorithm, cfg, sfl, params, batch_fn, sched,
                            key, rounds=rounds, mode=mode, chunk_size=chunk,
                            telemetry=sink,
                            chunk_callback=lambda i, p, s: infos.append(i),
                            **kw)
    return res, sink, infos


@pytest.mark.parametrize("mode", ["scan", "python"])
def test_sim_telemetry_bit_identical_to_chunkinfo_sync(setup, mode):
    """The acceptance gate: the sim producer's per-round durations are the
    SAME array values as ChunkInfo.round_times, flush by flush (per chunk
    in scan mode; python mode flushes — and therefore emits — per round)."""
    cfg, params, sfl, sched, batch_fn, key = setup
    _, sink, infos = _run_with_sink(cfg, sfl, params, batch_fn, sched, key,
                                    mode=mode)
    sims = sink.records("sim")
    expected = ([(0, 3), (3, 6), (6, 8)] if mode == "scan"
                else [(r, r + 1) for r in range(ROUNDS)])
    assert [(r.start, r.stop) for r in sims] == \
        [(i.start, i.stop) for i in infos] == expected
    for r, i in zip(sims, infos):
        assert np.array_equal(r.durations, i.round_times)   # bit-for-bit
        assert r.quorum_wait is None                        # sync path
        assert r.mode == mode
    # single-cohort schedule: one arrival latency per chunk, positive
    for r in sims:
        assert r.cohort_arrival is not None
        assert r.cohort_arrival.shape == (1,)
        assert float(r.cohort_arrival[0]) > 0


@pytest.mark.parametrize("timeline", ["dense", "sparse"])
def test_sim_telemetry_bit_identical_to_chunkinfo_async(setup, timeline):
    """Same gate on the async path (dense timeline and the sparse DES
    stream): durations == commit-interval round_times, and quorum_wait is
    populated from the timeline."""
    cfg, params, _, sched, batch_fn, key = setup
    sfl = _async_sfl(timeline)
    _, sink, infos = _run_with_sink(cfg, sfl, params, batch_fn, sched, key,
                                    mode="async",
                                    algorithm="async_mu_splitfed")
    sims = sink.records("sim")
    assert [(r.start, r.stop) for r in sims] == \
        [(i.start, i.stop) for i in infos]
    for r, i in zip(sims, infos):
        assert np.array_equal(r.durations, i.round_times)
        assert r.quorum_wait is not None
        assert r.quorum_wait.shape == r.durations.shape
        assert np.all(r.quorum_wait >= 0)


def test_measured_telemetry_brackets_every_chunk(setup):
    """The measured producer emits one record per chunk covering the same
    [start, stop) windows, with positive dispatch time and staged bytes."""
    cfg, params, sfl, sched, batch_fn, key = setup
    _, sink, infos = _run_with_sink(cfg, sfl, params, batch_fn, sched, key,
                                    mode="scan")
    meas = sink.records("measured")
    assert [(r.start, r.stop) for r in meas] == \
        [(i.start, i.stop) for i in infos]
    for r in meas:
        assert r.dispatch_seconds > 0
        assert r.staging_bytes > 0
        assert r.durations.shape == (r.n_rounds,)
        assert np.allclose(r.durations.sum(), r.dispatch_seconds)
        assert r.t_wall > 0


def test_telemetry_survives_controller_replans(setup):
    """AdaptiveTau re-plans at chunk boundaries; the sink keeps records
    from every segment and the controller's window sees telemetry."""
    cfg, params, _, sched, batch_fn, key = setup
    sfl = _async_sfl("sparse")
    seen = []

    class Probe(engine.AdaptiveTau):
        def update(self, round_idx, window, metrics):
            if window is not None:
                seen.append(window.telemetry)
            return super().update(round_idx, window, metrics)

    ctl = Probe(tau_max=8, source="measured")
    res, sink, _ = _run_with_sink(cfg, sfl, params, batch_fn, sched, key,
                                  mode="async",
                                  algorithm="async_mu_splitfed",
                                  controller=ctl)
    assert ctl.trace, "controller never re-planned"
    assert res.tau_per_round is not None
    # every controller step after the first chunk saw telemetry records,
    # including measured ones (its configured source)
    assert seen and all(len(w) > 0 for w in seen)
    assert all(any(r.source == "measured" for r in w) for w in seen)
    # sink retained records across re-plans: full round coverage per source
    for src in ("sim", "measured"):
        covered = sorted((r.start, r.stop) for r in sink.records(src))
        assert covered[0][0] == 0
        assert all(a[1] == b[0] for a, b in zip(covered, covered[1:]))


def test_adaptive_tau_measured_vs_sim_sources(setup):
    """source='measured' consumes wall-clock durations (machine-dependent)
    yet still produces a valid monotone plan; source='sim' is unchanged by
    the sink being attached."""
    cfg, params, sfl, sched, batch_fn, key = setup
    base = engine.AdaptiveTau(tau_max=8)
    r_nosink = engine.run_rounds("mu_splitfed", cfg, sfl, params, batch_fn,
                                 sched, key, rounds=ROUNDS, mode="scan",
                                 chunk_size=3, controller=base)
    sim_ctl = engine.AdaptiveTau(tau_max=8, source="sim")
    r_sim, _, _ = _run_with_sink(cfg, sfl, params, batch_fn, sched, key,
                                 mode="scan", controller=sim_ctl)
    assert np.array_equal(r_nosink.tau_per_round, r_sim.tau_per_round)
    meas_ctl = engine.AdaptiveTau(tau_max=8, source="measured")
    r_meas, _, _ = _run_with_sink(cfg, sfl, params, batch_fn, sched, key,
                                  mode="scan", controller=meas_ctl)
    assert r_meas.tau_per_round is not None
    assert np.all(r_meas.tau_per_round >= 1)


def test_adaptive_tau_rejects_unknown_source():
    with pytest.raises(ValueError):
        engine.AdaptiveTau(source="psychic")


def test_telemetry_across_checkpoint_resume(setup):
    """Kill after 4 rounds, resume from the checkpoint with a fresh sink:
    the resumed run's sim records start at the resume round, and together
    the two sinks tile [0, ROUNDS) with the SAME durations as an
    uninterrupted run."""
    cfg, params, sfl, sched, batch_fn, key = setup
    R, C = 6, 2
    _, full_sink, _ = _run_with_sink(cfg, sfl, params, batch_fn, sched, key,
                                     mode="scan", rounds=R, chunk=C)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        _, sink1, _ = _run_with_sink(cfg, sfl, params, batch_fn, sched, key,
                                     mode="scan", rounds=4, chunk=C,
                                     checkpointer=ck, ckpt_every=C)
        ck.wait()
        restored, meta = ck.restore(params, latest_step(d))
        _, sink2, _ = _run_with_sink(cfg, sfl, restored, batch_fn, sched,
                                     key, mode="scan", rounds=R, chunk=C,
                                     start_round=meta["step"] + 1)
    recs = sink1.records("sim") + sink2.records("sim")
    assert [(r.start, r.stop) for r in recs] == [(0, 2), (2, 4), (4, 6)]
    stitched = np.concatenate([r.durations for r in recs])
    reference = np.concatenate([r.durations
                                for r in full_sink.records("sim")])
    assert np.array_equal(stitched, reference)


def test_engine_spans_cover_hot_path(setup):
    """With a tracer installed, one run emits the stage/dispatch/flush
    span triple per chunk (and compile spans), properly nested."""
    cfg, params, sfl, sched, batch_fn, key = setup
    tr = SpanTracer()
    prev = install(tr)
    try:
        engine.run_rounds("mu_splitfed", cfg, sfl, params, batch_fn, sched,
                          key, rounds=ROUNDS, mode="scan", chunk_size=3)
    finally:
        install(prev)
    names = [r.name for r in tr.records()]
    for want in ("engine.stage", "engine.dispatch", "engine.flush"):
        assert names.count(want) == 3, (want, names)


def test_engine_spans_reach_the_profiler_trace(setup, tmp_path):
    """With the JAX profiler on and no tracer installed, a two-chunk scan
    run writes its host spans into the profiler's trace with their round
    attributes: prepare, then chunk > stage, dispatch, flush, then
    finish."""
    cfg, params, sfl, sched, batch_fn, key = setup
    prev = install(None)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0         # as the chip benchmark traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        engine.run_rounds("mu_splitfed", cfg, sfl, params, batch_fn, sched,
                          key, rounds=4, mode="scan", chunk_size=2)
    finally:
        jax.profiler.stop_trace()
        install(prev)
    ev = {}
    for name, t0, t1, stats in _host_events(str(tmp_path), "engine."):
        ev.setdefault(name, []).append((t0, t1, stats))
    rounds = {n: [(st["start"], st["stop"]) for _, _, st in es]
              for n, es in ev.items()}
    assert rounds == {"engine.prepare": [(0, 4)], "engine.finish": [(0, 4)],
                      **{f"engine.{n}": [(0, 2), (2, 4)] for n in
                         ("chunk", "stage", "dispatch", "flush")}}
    assert all("new_program" in st for _, _, st in ev["engine.dispatch"])
    (p0, p1, _), = ev["engine.prepare"]
    (f0, f1, _), = ev["engine.finish"]
    for c0, c1, st in ev["engine.chunk"]:
        assert p1 <= c0 and c1 <= f0
        for n in ("engine.stage", "engine.dispatch", "engine.flush"):
            (s0, s1), = [(a, b) for a, b, x in ev[n]
                         if x["start"] == st["start"]]
            assert c0 <= s0 and s1 <= c1, n


def test_telemetry_off_emits_nothing(setup):
    """No sink, no tracer: the engine takes the untimed path — nothing is
    recorded anywhere."""
    cfg, params, sfl, sched, batch_fn, key = setup
    tr = SpanTracer(enabled=False)
    prev = install(tr)
    try:
        res = engine.run_rounds("mu_splitfed", cfg, sfl, params, batch_fn,
                                sched, key, rounds=ROUNDS, mode="scan",
                                chunk_size=3)
    finally:
        install(prev)
    assert tr.records() == []
    assert res.round_loss.shape == (ROUNDS,)
