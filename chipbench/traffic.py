"""The one traffic generator: per-round client batches from a traffic file
and the run's seed.

A traffic file (``chipbench/traffic/<name>.json``) fixes the job: clients
M, per-client batch b, sequence length S, the algorithm's knobs, and the
token source. Tokens come from a seeded order-1 Markov language with
Zipfian unigrams (the shape of the program's own ``SyntheticLM``, rebuilt
here so that the benchmark owns its inputs): every token has ``branching``
successors, and with probability ``reset`` a token is drawn afresh from the
unigram.

Each client holds a local dataset of ``samples_per_client`` sequences,
made once at set-up (one Markov walk over all clients' rows at once).
Round r, client m samples b distinct rows of it with
``default_rng((seed, r, m))``, so staging a round is a gather, the same
seed gives the same batches, and every seed gives batches of the same
sizes.

Optional keys: ``schedule``, keyword arguments of the program's
``straggler.make_schedule`` (``straggler_scale``, ``deadline``,
``t_server``, ...; none: every client in every round).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Callable, Dict

import numpy as np

HERE = Path(__file__).resolve().parent

# every key a traffic file must give, with the type it must have
KEYS = {
    "clients": int, "batch": int, "seq": int, "tau": int,
    "perturbations": int, "noise": str, "client_mode": str,
    "aggregation": str, "chunk_size": int, "participation": float,
    "lr_server": float, "lr_client": float, "lr_global": float,
    "zo_eps": float, "branching": int, "reset": float,
    "samples_per_client": int,
}
OPTIONAL = {"schedule": dict}


def load(name: str) -> dict:
    """The traffic file ``traffic/<name>.json``, checked for every key."""
    doc = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    for k, t in KEYS.items():
        if k not in doc:
            raise ValueError(f"traffic {name}: missing key {k!r}")
        if not isinstance(doc[k], (int, float) if t is float else t):
            raise ValueError(f"traffic {name}: {k} must be {t.__name__}")
    for k, t in OPTIONAL.items():
        if not isinstance(doc.get(k, t()), t):
            raise ValueError(f"traffic {name}: {k} must be {t.__name__}")
    if doc["samples_per_client"] < doc["batch"]:
        raise ValueError(f"traffic {name}: samples_per_client < batch")
    return doc


def tokens_per_round(traffic: dict) -> int:
    return traffic["clients"] * traffic["batch"] * traffic["seq"]


class MarkovTokens:
    """Seeded Markov token source over ``vocab`` ids."""

    def __init__(self, vocab: int, seed: int, branching: int, reset: float):
        rng = np.random.default_rng((seed, 0x70CE))
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        self.cdf = np.cumsum(1.0 / ranks)
        self.cdf /= self.cdf[-1]
        self.successors = rng.integers(0, vocab, size=(vocab, branching),
                                       dtype=np.int64)
        self.vocab, self.seed = vocab, seed
        self.branching, self.reset = branching, reset

    def _unigram(self, rng, n):
        return np.minimum(np.searchsorted(self.cdf, rng.random(n)),
                          self.vocab - 1)

    def rows(self, n_rows: int, length: int) -> np.ndarray:
        """(n_rows, length + 1) token streams, walked side by side."""
        rng = np.random.default_rng((self.seed, 0x5EED))
        out = np.empty((n_rows, length + 1), np.int64)
        out[:, 0] = self._unigram(rng, n_rows)
        picks = rng.integers(0, self.branching, size=(n_rows, length))
        resets = rng.random((n_rows, length)) < self.reset
        fresh = self._unigram(rng, n_rows * length).reshape(n_rows, length)
        for t in range(length):
            nxt = self.successors[out[:, t], picks[:, t]]
            out[:, t + 1] = np.where(resets[:, t], fresh[:, t], nxt)
        return out


def make_batch_fn(traffic: dict, vocab: int, seed: int
                  ) -> Callable[[int], Dict[str, np.ndarray]]:
    """batch_fn(r) -> {"tokens", "labels"}: int32 (M, b, S) host arrays,
    stateless in r as the engine requires."""
    src = MarkovTokens(vocab, seed, traffic["branching"], traffic["reset"])
    M, b, S = traffic["clients"], traffic["batch"], traffic["seq"]
    N = traffic["samples_per_client"]
    pool = src.rows(M * N, S).astype(np.int32).reshape(M, N, S + 1)

    def batch_fn(r: int) -> Dict[str, np.ndarray]:
        toks = np.stack([
            pool[m, np.random.default_rng((seed, r, m)).choice(
                N, b, replace=False)] for m in range(M)])
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    return batch_fn
