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
