"""A whole run of a cell on the CPU at a tiny size, past the harness's
look for a chip: the sound program comes out correct, and a token
altered where the program produces it -- in the decode chunk, or as the
prefill's first token -- comes out not correct.

The tiny model is bfloat16 like the cells.  Over 12 seeds of about 45
served tokens each, sound runs read widest gaps of 0 to 0.033 and the
float8 control 0.151 to 0.317 (CPU); ``TINY_LIMIT`` sits between.  An
altered token lies below the best logit by about the logits' spread (~1).
"""
import json
from pathlib import Path

import numpy as np
import pytest

import repro.serving.engine as engine_mod
from bench import model, serve, traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY = {
    "architecture": "qwen2", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 256, "rope_theta": 1e6,
    "rms_norm_eps": 1e-06, "hidden_act": "silu", "initializer_range": 0.125,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "serving": {"page_size": 8, "ticks_per_sync": 4, "nan_guard": True,
                "prefix_caching": True, "eos_id": None},
    "prune": None,
}
MIXES = {
    "backlog": {"arrivals": {"kind": "backlog", "requests": 10},
                "prompt_len": {"values": [16], "weights": [1]},
                "max_new": {"uniform": [6, 14]}, "slots": 4,
                "max_seq_len": 32, "check_requests": 4},
    "poisson": {"arrivals": {"kind": "poisson", "rate_per_s": 6.0},
                "prompt_len": {"values": [8, 16, 24], "weights": [1, 1, 1]},
                "max_new": {"values": [4, 12], "weights": [1, 1]},
                "slots": 4, "max_seq_len": 40, "check_requests": 4},
}
SECONDS = 2.0
TINY_LIMIT = 0.08


@pytest.fixture
def tiny_cell(monkeypatch):
    def use(pruned: bool, mix: str):
        cfg = dict(TINY, prune={"sparsity": 0.5, "block": [32, 32], "selection_seed": 0}
                   if pruned else None)
        monkeypatch.setattr(model, "load_config", lambda name: cfg)
        monkeypatch.setattr(traffic, "load", lambda name: MIXES[mix])
        monkeypatch.setattr(serve, "load_limits",
                            lambda cell: {"max_logit_gap": {"limit": TINY_LIMIT}})
        return "bsr50-decode" if pruned else "dense-decode"
    return use


@pytest.mark.parametrize("pruned,mix", [(False, "poisson"), (True, "backlog")],
                         ids=["dense-poisson", "bsr-backlog"])
def test_sound_run_is_correct(tiny_cell, pruned, mix):
    cell = tiny_cell(pruned, mix)
    res = serve.run_cell(BENCH, cell, 2**32 + 5, SECONDS, False,
                         log=lambda s: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    reported = {m["name"] for m in serve.metrics_for(BENCH, cell, "end_to_end")}
    assert set(res["metrics"]) == reported
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _alter_decode(real):
    def chunk(*args, **kw):
        out = list(real(*args, **kw))
        out[0] = (out[0] + 1) % TINY["vocab_size"]     # the token block
        return tuple(out)
    return chunk


def _alter_prefill(real):
    def prefill(*args, **kw):
        first, ok, last, caches = real(*args, **kw)
        return (first + 1) % TINY["vocab_size"], ok, last, caches
    return prefill


@pytest.mark.parametrize("where,alter", [
    ("_decode_chunk", _alter_decode), ("_paged_prefill_step", _alter_prefill)])
def test_altered_token_is_not_correct(tiny_cell, monkeypatch, where, alter):
    cell = tiny_cell(True, "backlog")
    monkeypatch.setattr(engine_mod, where, alter(getattr(engine_mod, where)))
    res = serve.run_cell(BENCH, cell, 11, SECONDS, False, log=lambda s: None)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_run_fails_without_a_tpu():
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "dense-decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


class _ScriptedServer:
    """What the harness reads of the engine, scripted: two slots, one
    token per slot a step, the first token at admission, and then a stall
    after ``steps`` steps (the window closes with requests mid-stream and
    waiting).  A request of one token finishes inside the step that admits
    it, and the one named ``cancel`` is cancelled while it waits: neither
    ever shows in ``emitted``."""

    def __init__(self, steps: int, cancel: int):
        from repro.serving.scheduler import Request, RequestStatus

        self._Request, self._Status = Request, RequestStatus
        self.steps, self.cancel = steps, cancel
        self.waiting, self.slots, self.reqs = [], [None] * 2, {}
        self.emitted_now = {}

    def submit(self, prompt, max_new):
        rid = len(self.reqs)
        self.reqs[rid] = self._Request(rid=rid, prompt=prompt, max_new=max_new)
        self.waiting.append(rid)
        return rid

    def _finish(self, rid, status, tokens):
        req = self.reqs[rid]
        req.status, req.tokens = status, np.arange(tokens, dtype=np.int32)

    def step(self):
        if self.steps == 0:
            return
        self.steps -= 1
        if self.cancel in self.waiting:
            self.waiting.remove(self.cancel)
            self._finish(self.cancel, self._Status.CANCELLED, 0)
        for i, rid in enumerate(self.slots):
            if rid is None:
                continue
            self.emitted_now[rid] += 1
            if self.emitted_now[rid] == self.reqs[rid].max_new:
                self._finish(rid, self._Status.FINISHED, self.emitted_now.pop(rid))
                self.slots[i] = None
        for i in range(len(self.slots)):
            while self.slots[i] is None and self.waiting:
                rid = self.waiting.pop(0)
                req = self.reqs[rid]
                req.status, req.first_token_time = self._Status.ACTIVE, 1.0 + rid
                if req.max_new == 1:
                    self._finish(rid, self._Status.FINISHED, 1)
                else:
                    self.slots[i], self.emitted_now[rid] = rid, 1

    def busy(self):
        return bool(self.waiting) or any(s is not None for s in self.slots)

    def emitted(self):
        return dict(self.emitted_now)

    def request(self, rid):
        return self.reqs[rid]

    def counters(self):
        return {}


def test_window_records_requests_finished_between_stamps_and_waiting():
    """Request 0 (one token) finishes inside the step that admits it and
    request 3 is cancelled while waiting: neither ever holds a slot in
    ``emitted``, and both are recorded with their status and tokens.
    Requests 4 and 5 hold slots at the close, 6 to 8 still wait."""
    budgets = [1, 3, 2, 4, 9, 9, 9, 9, 9]
    server = _ScriptedServer(steps=4, cancel=3)
    requests = [traffic.Request(0.0, np.zeros(4, np.int32), m) for m in budgets]
    records, *_ = serve.serve(server, requests, 0.3)
    got = {rid: (r.status, len(r.stamps), r.first) for rid, r in records.items()}
    assert got == {
        0: ("finished", 1, 1.0), 1: ("finished", 3, 2.0), 2: ("finished", 2, 3.0),
        3: ("cancelled", 0, None), 4: ("active", 3, 5.0), 5: ("active", 2, 6.0),
        6: ("queued", 0, None), 7: ("queued", 0, None), 8: ("queued", 0, None)}
    assert [len(records[r].tokens) for r in (0, 1, 2, 3)] == [1, 3, 2, 0]
    assert all(records[r].tokens is None for r in (4, 5, 6, 7, 8))
    stamps = sorted({t for r in records.values() for t in r.stamps})
    assert len(stamps) == 4 and records[0].stamps == stamps[:1]
