"""Observability substrate: telemetry records, span tracing, metrics.

The ROADMAP's sim-to-real seam: every control decision in the engine
(AdaptiveTau re-planning τ, future cut×τ co-planners) historically read
the schedule's *simulated* delays — nothing observed what the hardware
actually did. This package is the measurement layer both the simulator
and the real engine feed:

  telemetry   RoundTelemetry (per-chunk durations, quorum waits,
              per-cohort arrival latencies, staging bytes, host-prefetch
              vs device-scan overlap) + TelemetrySink, a ring-buffer hub
              with named producers — the simulator is just one of them.
  trace       a nestable, thread-safe span tracer (perf_counter) with
              Chrome-trace / JSONL export, near-zero-cost when disabled,
              installed over the engine hot path (chunk dispatch, DES
              streaming, subset staging, fleet placement); its spans also
              land in any JAX profiler trace, on the device's clock.
  metrics     a counters/gauges/histograms registry surfaced by
              launch/train.py (--telemetry) and launch/serve.py (stats).
  measure     the (seconds, peak_bytes) perf_counter + tracemalloc
              helper every benchmark row is measured with.
  runlog      structured JSONL run log (per-round rows + per-chunk
              telemetry), resume-safe (never duplicates rounds).

Nothing here imports the engine, nor jax at import time (``trace.span``
imports jax.profiler on its first call, to ask whether a profiler is
collecting): probes are host-side and read at chunk boundaries only — the
`telemetry-purity` lint rule (repro.analysis) enforces that no probe or
host-sync coercion lands inside a jit-traced body.
"""
from repro.obs.measure import Measurement, measure
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               get_registry)
from repro.obs.runlog import RunLog, read_jsonl
from repro.obs.telemetry import RoundTelemetry, TelemetrySink
from repro.obs.trace import SpanRecord, SpanTracer, get_tracer, install, span

__all__ = [
    "Measurement", "measure",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "RunLog", "read_jsonl",
    "RoundTelemetry", "TelemetrySink",
    "SpanRecord", "SpanTracer", "get_tracer", "install", "span",
]
