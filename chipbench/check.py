"""The comparison that decides ``correct``.

A training cell's program readings come from the first call of the timed
path (the compiled chunk program driven by ``engine.run_rounds``): the
per-round losses of its C rounds, each client's round-start loss in the
first round (the program's per-client loss metric), and the parameters'
change over the call. The plain reference follows the same C rounds from
the same weights and batches. The numbers:

  * ``loss_gap``: the worst round's |L_program - L_reference| / L_reference;
  * ``client_loss_gap``: the root mean square over the first round's
    clients of each client's |L_program - L_reference| / L_reference;
    both sides start that round from the same weights, so it measures the
    forwards alone;
  * ``update_gap``: the worst leaf's |n_program - n_reference| /
    max(n_reference, median leaf n_reference), n = |x_C - x_0|_2 per leaf
    of each half (a stacked leaf's client layers and server layers apart).
    Leaves the reference leaves unmoved (n under a thousandth of the
    median leaf's) are left out; none are at these sizes, since every
    leaf takes noise.

A client that the schedule leaves out of the first round has no reference
loss (nan) and is left out of ``client_loss_gap``.

Each number that the cell's limits (``limits/<cell>.json``) name is held
to its limit; a number they do not name is printed and not compared (see
PERF.md for why). No limits, a missing number or a non-finite one is not
correct.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
NUMBERS = ("loss_gap", "client_loss_gap", "update_gap")


def load_limits(cell: str) -> Optional[dict]:
    path = HERE / "limits" / f"{cell}.json"
    return json.loads(path.read_text()) if path.exists() else None


def _worst(p, r) -> float:
    """max_i |p_i - r_i| / max(|r_i|, median |r|), over the i with |r_i|
    at least a thousandth of the median."""
    p = np.asarray(p, np.float64)
    r = np.asarray(r, np.float64)
    scale = np.abs(r)
    med = float(np.median(scale))
    keep = scale >= 1e-3 * med
    return float(np.max(np.abs(p - r)[keep] / np.maximum(scale, med)[keep]))


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    cp = np.asarray(prog["client_loss"], np.float64)
    cr = np.asarray(ref["client_loss"], np.float64)
    seen = np.isfinite(cr)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "client_loss_gap": float(np.sqrt(np.mean(
            np.square((cp - cr)[seen] / cr[seen])))),
        "update_gap": _worst(prog["norms"], ref["norms"]),
    }


def verdict(nums: Dict[str, float], limits: Optional[dict]):
    """(correct, {name: {"value", "limit"}}) in NUMBERS order; the numbers
    compared come last."""
    limits = {k: v for k, v in (limits or {}).items() if v is not None}
    ok = bool(limits) and set(limits) <= set(NUMBERS)
    order = sorted(NUMBERS, key=lambda k: k in limits)
    checks = {}
    for k in order:
        v = nums.get(k, float("nan"))
        checks[k] = {"value": v, "limit": limits.get(k)}
        if k in limits:
            ok = ok and math.isfinite(v) and v <= limits[k]
    return ok, checks


def lines(checks: Dict) -> list:
    return [f"check {k} {c['value']!r} limit {c['limit']!r}"
            for k, c in checks.items()]
