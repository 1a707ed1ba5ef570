"""Reduce a profiler trace (``.xplane.pb``) of the measured window to the
numbers the per-layer readers take.

On a v5e the device plane ``/device:TPU:<i>`` has a line "XLA Ops" whose
events are named by the HLO instruction's text, e.g.
``%fusion.8 = (...) fusion(...), kind=kOutput, calls=...`` or
``%f.1 = bf16[4096,1024]{...} custom-call(...),
custom_call_target="tpu_custom_call", ...``. Control-flow ops (while,
conditional, call) appear as events that span the ops of their bodies, so
they are left out: only the ops that do work count. The window is the host
span the benchmark writes around it (``chipbench.window``); ops are clipped
to it. From them:

  * busy_s: the union of op intervals, averaged over the chips;
  * matmul_s / other_s: summed op time, split by whether the op is a
    matmul: a dot or convolution, or an output fusion (``kind=kOutput``,
    the TPU's fusion rooted in a convolution); other_s is everything else;
  * kernel_s: summed time of Pallas kernels (``tpu_custom_call``) by
    family; the ZO replay kernel is the one whose operands are
    (u32[n] seeds, f32[n] coefficients, a [rows, 1024] leaf);
  * top_ops: the ten instructions that took most time;
  * top_gaps: the ten longest idle gaps, each named by the innermost host
    event at the gap's middle: a benchmark span (``chipbench.stage`` is
    the traffic generator feeding the engine) or the runtime's own.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

CONTAINERS = ("while", "conditional", "call")
_REPLAY = re.compile(r"custom-call\(u32\[\d+\]\S* \S+, f32\[\d+\]\S* \S+, "
                     r"[a-z0-9]+\[\d+,1024\]")


class Ev(NamedTuple):
    name: str
    start: int          # ns, on the profiler's clock
    end: int
    device: str = ""    # the plane, for device ops


def opcode(name: str) -> str:
    """The HLO opcode of an "XLA Ops" event name ('' if not HLO text)."""
    i = name.find(" = ")
    if not name.startswith("%") or i < 0:
        return ""
    j = i + 3
    if name[j:j + 1] == "(":                  # tuple shape: skip to its ')'
        depth = 0
        for j in range(j, len(name)):
            depth += {"(": 1, ")": -1}.get(name[j], 0)
            if depth == 0:
                break
        j += 1
    else:
        j = name.find(" ", j)
    m = re.match(r"\s*([a-z][a-z0-9\-_]*)\(", name[j:])
    return m.group(1) if m else ""


def short(name: str) -> str:
    """'%fusion.8 = (...) fusion(...), kind=kOutput, ...' -> 'fusion.8
    fusion kOutput': the instruction, its opcode and its fusion kind."""
    if not name.startswith("%"):
        return name
    kind = re.search(r"kind=(k\w+)", name)
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return " ".join(x for x in (name[1:name.find(" ")], opcode(name),
                                kind and kind.group(1),
                                target and target.group(1)) if x)


def is_matmul(name: str) -> bool:
    op = opcode(name)
    return op in ("convolution", "dot") or (op == "fusion"
                                            and "kind=kOutput" in name)


def kernel_family(name: str) -> Optional[str]:
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    return "zo_replay" if _REPLAY.search(name) else "other_kernel"


def load(path: str) -> Tuple[List[Ev], List[Ev]]:
    """(device ops, host events) of one .xplane.pb file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.extend(Ev(e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns), plane.name)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Ev(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns))
                            for e in line.events)
    return dev, host


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _gap_name(host: List[Ev], t: int) -> str:
    around = [h for h in host if h.start <= t < h.end]
    if not around:
        return "no host span"
    return min(around, key=lambda h: h.end - h.start).name


def reduce_events(dev: List[Ev], host: List[Ev], window: str) -> Dict:
    spans = [h for h in host if h.name == window]
    if not spans:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = spans[0].start, spans[0].end
    ops = [Ev(e.name, max(e.start, w0), min(e.end, w1), e.device)
           for e in dev if e.end > w0 and e.start < w1
           and opcode(e.name) not in CONTAINERS]
    devices = sorted({e.device for e in ops}) or [""]
    n_dev = len(devices)
    busy = {d: union((e.start, e.end) for e in ops if e.device == d)
            for d in devices}
    busy_ns = sum(sum(b - a for a, b in iv) for iv in busy.values()) / n_dev
    matmul = sum(e.end - e.start for e in ops if is_matmul(e.name))
    total = sum(e.end - e.start for e in ops)
    kern: Dict[str, float] = defaultdict(float)
    by_name: Dict[str, float] = defaultdict(float)
    for e in ops:
        by_name[short(e.name)] += e.end - e.start
        k = kernel_family(e.name)
        if k:
            kern[k] += e.end - e.start
    # idle gaps of the first chip, named by what the host was doing
    edges = [w0] + [x for ab in busy[devices[0]] for x in ab] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    inner = [h for h in host if h.name != window and h.end > w0
             and h.start < w1]
    top_gaps = [[_gap_name(inner, (a + b) // 2), (b - a) / 1e9]
                for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "n_ops": len(ops),
        "n_gaps": len(gaps),
        "matmul_s": matmul / 1e9 / n_dev,
        "other_s": (total - matmul) / 1e9 / n_dev,
        "kernel_s": {k: v / 1e9 / n_dev for k, v in kern.items()},
        "top_ops": [[k, v / 1e9 / n_dev] for k, v in
                    sorted(by_name.items(), key=lambda kv: -kv[1])[:10]],
        "top_gaps": top_gaps,
    }


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found "
                         f"{len(paths)}")
    return paths[0]


def reduce_dir(trace_dir: str, window: str) -> Dict:
    dev, host = load(find_xplane(trace_dir))
    return reduce_events(dev, host, window)
