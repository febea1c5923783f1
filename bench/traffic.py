"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws the requests of one run from the
seed.

Every seed gets the same multiset of (prompt length, output budget)
pairs and of inter-arrival gaps -- stratified quantiles of the mix's
distributions -- in a different order, with different token ids.  So seeds vary which
request comes when, not how much work a run holds.

A mix file holds:

* ``arrivals``: ``{"kind": "backlog", "requests": n}`` -- n requests all
  due when the window opens (a closed backlog) -- or
  ``{"kind": "poisson", "rate_per_s": r}`` -- an open loop with
  exponential gaps at rate r over the window's length;
* ``prompt_len`` and ``max_new``: ``{"values": [...], "weights": [...]}``
  or ``{"uniform": [lo, hi]}`` (whole numbers, both ends included);
* ``slots`` and ``max_seq_len``: the decode batch rows and the longest
  prompt + output a request may hold;
* ``check_requests``: how many finished requests the correctness check
  samples.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Request:
    due: float              # seconds after the window opens
    prompt: np.ndarray      # (prompt_len,) int32 token ids
    max_new: int


def load(name: str) -> Dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _multiset(dist: Dict, n: int) -> np.ndarray:
    """n whole numbers whose histogram follows ``dist`` as closely as n
    allows (largest remainders for weights, midpoint quantiles for a
    uniform range)."""
    if "uniform" in dist:
        lo, hi = dist["uniform"]
        q = (np.arange(n) + 0.5) / n
        return np.floor(lo + q * (hi - lo + 1)).astype(np.int64)
    values = np.asarray(dist["values"], np.int64)
    w = np.asarray(dist["weights"], np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return np.repeat(values, counts)


def lengths(mix: Dict) -> List[int]:
    """Every prompt length the mix can send: the prefill shapes to warm."""
    dist = mix["prompt_len"]
    if "uniform" in dist:
        lo, hi = dist["uniform"]
        return list(range(lo, hi + 1))
    return sorted(int(v) for v, w in zip(dist["values"], dist["weights"])
                  if w > 0)


def generate(mix: Dict, vocab: int, seed: int, seconds: float) -> List[Request]:
    """The requests of one run, sorted by due time."""
    rng = np.random.default_rng([int(seed), 1])
    arr = mix["arrivals"]
    if arr["kind"] == "backlog":
        n = int(arr["requests"])
        dues = np.zeros(n)
    elif arr["kind"] == "poisson":
        rate = float(arr["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        gaps = rng.permutation(gaps)
        dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    # one fixed pairing of lengths and budgets for every seed; the seed
    # orders the pairs
    pairs = np.stack([_multiset(mix["prompt_len"], n),
                      np.random.default_rng(0).permutation(
                          _multiset(mix["max_new"], n))], axis=1)
    plen, new = rng.permutation(pairs).T
    return [Request(float(d), rng.integers(0, vocab, size=int(p),
                                           dtype=np.int32), int(m))
            for d, p, m in zip(dues, plen, new)]
