"""The traffic generator: every seed gets the same work in another order."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _accepted_tok_s():
    """The highest output_tok_s that an accepted line of PERF_LEDGER.jsonl
    reads for a cell on each traffic mix, on either side of the line."""
    best = {}
    ledger = ROOT / "PERF_LEDGER.jsonl"
    lines = ledger.read_text().splitlines() if ledger.exists() else []
    for line in filter(str.strip, lines):
        rec = json.loads(line)
        if rec.get("verdict") != "accepted" or not rec.get("traffic"):
            continue
        for v in rec.get("end_to_end", {}).get("output_tok_s", []):
            if v is not None:
                best[rec["traffic"]] = max(best.get(rec["traffic"], 0.0), v)
    return best


ACCEPTED_TOK_S = _accepted_tok_s()
# every backlog mix a cell uses, once a cell on it has an accepted line
BACKLOG_MIXES = sorted({
    w["traffic"] for w in BENCH["workloads"]
    if traffic.load(w["traffic"])["arrivals"]["kind"] == "backlog"}
    & set(ACCEPTED_TOK_S))

SEEDS = [0, 7, 2**31 + 12345, 2**40 + 3]
# an open-loop mix: Poisson arrivals, heavy-tailed prompts and outputs
POISSON = {
    "arrivals": {"kind": "poisson", "rate_per_s": 0.85},
    "prompt_len": {"values": [128, 256, 512, 1024],
                   "weights": [0.3, 0.35, 0.25, 0.1]},
    "max_new": {"values": [16, 32, 64, 128, 256],
                "weights": [0.15, 0.25, 0.3, 0.2, 0.1]},
    "slots": 16, "max_seq_len": 1280, "check_requests": 24,
}


def _mix(name):
    return POISSON if name == "chat-poisson" else traffic.load(name)


@pytest.mark.parametrize("mix_name", ["decode-backlog", "chat-poisson"])
def test_seeds_share_one_multiset(mix_name):
    mix = _mix(mix_name)
    runs = [traffic.generate(mix, 151936, s, 51.0) for s in SEEDS]
    sizes = {tuple(sorted((len(r.prompt), r.max_new) for r in run))
             for run in runs}
    assert len(sizes) == 1
    if mix["arrivals"]["kind"] == "poisson":
        n = len(runs[0])
        rate = mix["arrivals"]["rate_per_s"]
        quantiles = set(np.round(-np.log1p(-(np.arange(n) + 0.5) / n) / rate, 9))
        for run in runs:       # the gaps between arrivals: all but one of them
            gaps = np.round(np.diff([r.due for r in run]), 9)
            assert len(set(gaps)) == n - 1 and set(gaps) <= quantiles
    orders = {tuple(r.max_new for r in run) for run in runs}
    assert len(orders) == len(SEEDS)


def test_same_seed_same_requests():
    mix = POISSON
    a = traffic.generate(mix, 1000, 2**33, 20.0)
    b = traffic.generate(mix, 1000, 2**33, 20.0)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in a)


def test_mix_fits_its_context():
    for name in ("decode-backlog", "chat-poisson"):
        mix = _mix(name)
        run = traffic.generate(mix, 100, 1, 51.0)
        assert max(len(r.prompt) + r.max_new for r in run) <= mix["max_seq_len"]
        assert set(len(r.prompt) for r in run) <= set(traffic.lengths(mix))


def test_multiset_follows_weights():
    got = traffic._multiset({"values": [1, 2, 3], "weights": [0.5, 0.3, 0.2]},
                            10)
    assert sorted(got.tolist()) == [1] * 5 + [2] * 3 + [3] * 2
    uni = traffic._multiset({"uniform": [128, 512]}, 385)
    assert uni.min() == 128 and uni.max() == 512
    assert len(set(uni.tolist())) == 385


def test_poisson_rate_and_window():
    mix = dict(POISSON,
               arrivals={"kind": "poisson", "rate_per_s": 2.0})
    run = traffic.generate(mix, 100, 3, 40.0)
    assert len(run) == 80
    dues = [r.due for r in run]
    assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 40.0


@pytest.mark.parametrize("mix_name", BACKLOG_MIXES)
def test_backlog_outlasts_four_times_the_accepted_rate(mix_name):
    """A closed backlog that drains inside the window caps output_tok_s at
    its budgeted tokens over the window, and leaves the traced span at the
    window's end without a decode chunk to read.  So a backlog mix holds
    at least four times what the best accepted cell on it serves in a
    window.  The rate is read from the ledger, so the test tightens as
    faster changes are accepted: a change that takes a cell past a quarter
    of its mix's drain rate deepens the mix first."""
    mix = traffic.load(mix_name)
    budget = sum(r.max_new for r in traffic.generate(
        mix, 2, 0, BENCH["run_seconds"]))
    assert budget / BENCH["run_seconds"] >= 4 * ACCEPTED_TOK_S[mix_name]
