"""Split a traced window by the program's own names: device time by the
MU-SplitFed round's five phases, idle time by the host span it fell in.

Device phases. The program runs each step of a round under a
``jax.named_scope`` (``core/splitfed.py``): ``sfl.client_forward``,
``sfl.server_eval``, ``sfl.server_tau``, ``sfl.zo_backprop`` and
``sfl.replay``. XLA writes the scope path into each instruction's op_name
metadata, and a v5e trace keeps it as the ``tf_op`` stat of the event
metadata that the "XLA Ops" events point to, e.g.
``jit(run_chunk)/while/body/closed_call/sfl.server_tau/while/body/...``
(a scope under a transform reads ``vmap(sfl.client_forward)``).
``jax.profiler.ProfileData`` gives the events but not their metadata's
stats, so ``tf_ops`` decodes them from the ``.xplane.pb`` file itself: the
XSpace protobuf, read field by field (``tsl/profiler/protobuf/xplane.proto``),
keyed by the program id (a stat of the same metadata) and the op's event
name. Each op is then charged to the innermost of the five scopes in its
``tf_op`` path, found through the "XLA Modules" event (``jit_run_chunk(<program
id>)``) that encloses it, or to ``unscoped`` where it has none (ops the
compiler adds, such as copies, and work outside the round). The ops are those
``trace_reduce.reduce_events`` counts (clipped to the window, control flow
left out), so the six totals add up to its matmul_s + other_s.

Idle time. A program span is a host event named ``engine.*``, ``events.*``,
``loader.*``, ``fleet.*`` or ``chipbench.*`` other than the window itself:
``repro.obs.span`` writes the engine's as profiler annotations. Each idle
gap of the first chip is named ``<innermost program span>/<innermost runtime
event>`` at its middle, with ``no host span`` where no program span covers
it; ``unspanned_idle_s`` is the idle time, averaged over the chips, that no
program span covers.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Tuple

from chipbench import trace_reduce

SCOPES = ("sfl.client_forward", "sfl.server_eval", "sfl.server_tau",
          "sfl.zo_backprop", "sfl.replay")
UNSCOPED = "unscoped"
PROGRAM_SPANS = ("engine.", "events.", "loader.", "fleet.", "chipbench.")
NO_SPAN = "no host span"

_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, SCOPES))
                    + r")(?=[/):]|$)")
_MODULE_ID = re.compile(r"\((\d+)\)$")


class Op(NamedTuple):
    name: str
    start: int          # ns, on the profiler's clock
    end: int
    device: str
    program: int        # id of the enclosing XLA module, -1 if none


def scope_of(tf_op: str) -> str:
    """The innermost of SCOPES in an op_name path, or UNSCOPED."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else UNSCOPED


# -- the xplane protobuf, read field by field ------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int = 0, end: int = -1):
    """(field number, value) of one message: a varint as an int, a
    length-delimited field as its (start, end) in ``buf``."""
    end = len(buf) if end < 0 else end
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield field, val


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _plane_tf_ops(buf, a: int, b: int) -> Dict[Tuple[int, str], str]:
    name, event_md, stat_names = "", [], {}
    for f, v in _fields(buf, a, b):
        if f == 2:
            name = _str(buf, v)
        elif f == 4:
            event_md.append(v)
        elif f == 5:                   # map entry: key 1, XStatMetadata 2
            for kf, kv in _fields(buf, *v):
                if kf == 2:
                    md = dict(_fields(buf, *kv))
                    stat_names[md.get(1, 0)] = (_str(buf, md[2])
                                                if 2 in md else "")
    out: Dict[Tuple[int, str], str] = {}
    if not name.startswith("/device:"):
        return out
    for entry in event_md:             # map entry: key 1, XEventMetadata 2
        for kf, kv in _fields(buf, *entry):
            if kf != 2:
                continue
            ev_name, program, tf_op = "", -1, ""
            for f, v in _fields(buf, *kv):
                if f == 2:
                    ev_name = _str(buf, v)
                elif f == 5:
                    st = dict(_fields(buf, *v))
                    which = stat_names.get(st.get(1))
                    if which == "tf_op":
                        tf_op = (_str(buf, st[5]) if 5 in st
                                 else stat_names.get(st.get(7), ""))
                    elif which == "program_id":
                        program = st.get(3, st.get(4, -1))
            if tf_op:
                out[(program, ev_name)] = tf_op
    return out


def tf_ops(path: str) -> Dict[Tuple[int, str], str]:
    """(program id, op event name) -> tf_op of every device op whose
    event metadata carries one."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out: Dict[Tuple[int, str], str] = {}
    for f, v in _fields(buf):
        if f == 1:
            out.update(_plane_tf_ops(buf, *v))
    return out


# -- events ---------------------------------------------------------------

def load(path: str) -> Tuple[List[Op], List[trace_reduce.Ev]]:
    """(device ops with their program, host events) of one .xplane.pb."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           int(m.group(1)) if m else -1)
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ())
                          for m in [_MODULE_ID.search(e.name)])
            starts = [m[0] for m in mods]
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                t0 = int(e.start_ns)
                k = bisect.bisect_right(starts, t0) - 1
                prog = mods[k][2] if k >= 0 and t0 < mods[k][1] else -1
                ops.append(Op(e.name, t0, int(t0 + e.duration_ns),
                              plane.name, prog))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(trace_reduce.Ev(e.name, int(e.start_ns),
                                            int(e.start_ns + e.duration_ns))
                            for e in line.events)
    return ops, host


def _is_program(name: str, window: str) -> bool:
    return name.startswith(PROGRAM_SPANS) and name != window


def gap_name(host: List[trace_reduce.Ev], t: int, window: str) -> str:
    """``<innermost program span>/<innermost runtime event>`` at time t."""
    around = [h for h in host if h.start <= t < h.end and h.name != window]
    spans = [h for h in around if _is_program(h.name, window)]
    runtime = [h for h in around if not _is_program(h.name, window)]
    name = (min(spans, key=lambda h: h.end - h.start).name if spans
            else NO_SPAN)
    if runtime:
        name += "/" + min(runtime, key=lambda h: h.end - h.start).name
    return name


def _covered(a: int, b: int, spans: List[Tuple[int, int]]) -> int:
    """ns of [a, b) inside the union ``spans``."""
    return sum(max(0, min(b, s1) - max(a, s0)) for s0, s1 in spans)


def reduce_events(ops: List[Op], host: List[trace_reduce.Ev],
                  names: Dict[Tuple[int, str], str], window: str) -> Dict:
    """Phase seconds, unspanned idle seconds and named gaps of the window
    (seconds averaged over the chips)."""
    wins = [h for h in host if h.name == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = wins[0].start, wins[0].end
    ops = [o._replace(start=max(o.start, w0), end=min(o.end, w1))
           for o in ops if o.end > w0 and o.start < w1
           and trace_reduce.opcode(o.name) not in trace_reduce.CONTAINERS]
    devices = sorted({o.device for o in ops}) or [""]
    n_dev = len(devices)
    phase: Dict[str, float] = defaultdict(float)
    for o in ops:
        phase[scope_of(names.get((o.program, o.name), ""))] += o.end - o.start
    inner = [h for h in host if h.end > w0 and h.start < w1]
    spans = trace_reduce.union((max(h.start, w0), min(h.end, w1))
                               for h in inner if _is_program(h.name, window))
    unspanned = 0
    gaps0: List[Tuple[int, int]] = []
    for d in devices:
        busy = trace_reduce.union((o.start, o.end) for o in ops
                                  if o.device == d)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        unspanned += sum(b - a - _covered(a, b, spans) for a, b in gaps)
        if d == devices[0]:
            gaps0 = gaps
    top = sorted(gaps0, key=lambda g: g[0] - g[1])[:10]
    return {
        "phase_s": {k: phase.get(k, 0.0) / 1e9 / n_dev
                    for k in SCOPES + (UNSCOPED,)},
        "unspanned_idle_s": unspanned / 1e9 / n_dev,
        "top_gaps": [[gap_name(inner, (a + b) // 2, window), (b - a) / 1e9]
                     for a, b in top],
    }


def reduce_dir(trace_dir: str, window: str) -> Dict:
    path = trace_reduce.find_xplane(trace_dir)
    ops, host = load(path)
    return reduce_events(ops, host, tf_ops(path), window)
