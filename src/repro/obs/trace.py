"""Nestable span tracer over the engine hot path.

Design constraints, in order:

1. **Near-zero cost when disabled.** Every instrumentation point calls the
   module-level ``span(name, **attrs)``; when no enabled tracer is
   installed and no JAX profiler is collecting it returns one shared no-op
   context manager — the cost is a global load, an attribute check, one
   ``TraceAnnotation.is_enabled()`` call, and the kwargs dict Python builds
   anyway. No allocation, no clock read, no lock. The engine's CI overhead
   gate (benchmarks/bench_telemetry.py) holds the *enabled* path to <= 2%
   on the sparse timeline; the disabled path is gated by a unit test.
2. **Thread-safe nesting.** The engine's host side is single-threaded
   today, but checkpointing is async and multi-host fleets won't be: the
   span stack is thread-local (so ``depth``/parent attribution is per
   thread) and the finished-record list is appended under a lock.
3. **Standard exports.** ``export_chrome`` writes the Chrome trace-event
   JSON (load in chrome://tracing or https://ui.perfetto.dev);
   ``export_jsonl`` writes one span per line for ad-hoc processing.

4. **The profiler's clock.** While a JAX profiler is collecting
   (``jax.profiler.start_trace``), every span also opens a
   ``jax.profiler.TraceAnnotation`` of the same name and attributes, so
   the engine's spans lie in the device trace beside the device's ops.
   jax is imported on the first ``span()`` call, not with this module.

Spans measure HOST time (time.perf_counter). Device work is measured by
bracketing dispatch with ``jax.block_until_ready`` at chunk boundaries —
inside jit-traced code a span would fire at trace time only, which is why
the ``telemetry-purity`` lint rule forbids probes there (device phases are
named with ``jax.named_scope`` instead, see core/splitfed.py).
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional


class SpanRecord(NamedTuple):
    """One finished span."""
    name: str
    start: float         # perf_counter seconds at entry
    duration: float      # seconds
    thread: int          # OS thread ident
    depth: int           # nesting depth within its thread (0 = top level)
    attrs: Dict[str, Any]


class _NullSpan:
    """The shared disabled-path context manager: enters and exits for free
    and swallows nothing (exceptions propagate)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):             # symmetric API with _Span
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    """A live span; created only when a tracer is enabled or the profiler
    is collecting. ``tracer`` None: profiler annotation only."""
    __slots__ = ("_tracer", "name", "attrs", "_t0", "_depth", "_profile",
                 "_annot")

    def __init__(self, tracer: Optional["SpanTracer"], name: str,
                 attrs: Dict[str, Any], profile: bool = False):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._profile = profile
        self._annot = None

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (e.g. bytes staged)."""
        self.attrs.update(attrs)
        if self._annot is not None:
            self._annot.set_metadata(**attrs)
        return self

    def __enter__(self) -> "_Span":
        if self._profile:
            # a TraceAnnotation starts its clock when it is constructed
            self._annot = _TraceAnnotation(self.name, **self.attrs)
            self._annot.__enter__()
        if self._tracer is not None:
            stack = self._tracer._stack()
            self._depth = len(stack)
            stack.append(self)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        tracer = self._tracer
        if tracer is not None:
            t1 = time.perf_counter()
            tracer._stack().pop()
            rec = SpanRecord(self.name, self._t0, t1 - self._t0,
                             threading.get_ident(), self._depth, self.attrs)
            with tracer._lock:
                tracer._records.append(rec)
        if self._annot is not None:
            self._annot.__exit__(*exc)
        return False


class SpanTracer:
    """Collects SpanRecords; install one with ``obs.trace.install``."""

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        self._records: List[SpanRecord] = []
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def records(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._records)

    def clear(self) -> None:
        with self._lock:
            self._records.clear()

    # -- exports ----------------------------------------------------------

    def export_jsonl(self, path: str) -> int:
        """One span per line: {name, start, duration, thread, depth, attrs}.
        Returns the number of spans written."""
        recs = self.records()
        with open(path, "w", encoding="utf-8") as fh:
            for r in recs:
                fh.write(json.dumps(r._asdict()) + "\n")
        return len(recs)

    def export_chrome(self, path: str) -> int:
        """Chrome trace-event format ('X' complete events, µs timebase) —
        loadable in chrome://tracing / perfetto. Returns the span count."""
        recs = self.records()
        events = [{"name": r.name, "ph": "X", "pid": 0, "tid": r.thread,
                   "ts": r.start * 1e6, "dur": r.duration * 1e6,
                   "args": r.attrs} for r in recs]
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, fh)
        return len(recs)


# ---------------------------------------------------------------------------
# the module-level instrumentation surface
# ---------------------------------------------------------------------------

_ACTIVE: Optional[SpanTracer] = None
_TraceAnnotation = None     # jax.profiler.TraceAnnotation, on first span()


def install(tracer: Optional[SpanTracer]) -> Optional[SpanTracer]:
    """Install (or, with None, remove) the process-wide tracer; returns the
    previously installed one so callers can restore it."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    return prev


def get_tracer() -> Optional[SpanTracer]:
    return _ACTIVE


def span(name: str, **attrs):
    """The hot-path probe: ``with span('engine.chunk', start=r0): ...``.
    Records into the installed tracer, and writes a TraceAnnotation while
    the JAX profiler collects. Free when neither is on."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    t = _ACTIVE
    if t is not None and not t.enabled:
        t = None
    profiling = _TraceAnnotation.is_enabled()
    if t is None and not profiling:
        return _NULL_SPAN
    return _Span(t, name, attrs, profiling)
