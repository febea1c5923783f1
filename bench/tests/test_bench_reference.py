"""The plain Qwen2 reference against the program at a tiny size on the CPU:
its forward against ``lm_forward``, and the served tokens of an engine
run (paged prefill, then decode through the page pool) on dense and on
BSR-packed weights.

Everything here is float32 on the CPU, where XLA computes float32
matmuls in full float32: the two sides differ only in the order of
their sums, about 1e-6 relative on logits of size ~10, so the limits
below (1e-4 relative, 1e-3 absolute gap) hold that with a hundredfold
margin, while a dropped bias, norm scale, rotary term or tile moves the
logits by O(1).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, model
from bench.references import qwen2
from bench.system import Server

TINY = {
    "architecture": "qwen2", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "hidden_act": "silu", "initializer_range": 0.125,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "serving": {"page_size": 8, "ticks_per_sync": 4, "nan_guard": True,
                "prefix_caching": True, "eos_id": None},
    "prune": None,
}
PRUNED = dict(TINY, prune={"sparsity": 0.5, "block": [32, 32], "selection_seed": 0})
MIX = {"slots": 3, "max_seq_len": 48}
REL = 1e-4
GAP = 1e-3


def _ref_logits(cfg, w, tokens):
    x = qwen2.hidden(w, jnp.asarray(tokens), cfg)
    return np.asarray(x @ qwen2.head(w, cfg))


def test_reference_matches_lm_forward():
    from repro.models import lm_forward

    server = Server(TINY, "tiny", MIX, seed=5)
    tokens = np.random.default_rng(0).integers(0, 256, size=20)
    got = np.asarray(lm_forward(server.engine.params,
                                {"tokens": jnp.asarray(tokens[None])},
                                server.model_cfg)[0][0])
    want = _ref_logits(TINY, model.weights(TINY, 5), tokens)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < REL


def test_reference_sees_a_dropped_bias():
    w = model.weights(TINY, 5)
    tokens = np.arange(20) % 256
    broken = dict(w, bk=jnp.zeros_like(w["bk"]))
    a, b = _ref_logits(TINY, w, tokens), _ref_logits(TINY, broken, tokens)
    assert np.linalg.norm(a - b) / np.linalg.norm(a) > 100 * REL


def test_pruning_keeps_half_the_tiles_and_zeroes_the_rest():
    w = model.weights(PRUNED, 5)
    dense = model.weights(TINY, 5)
    kept = total = 0
    for name in qwen2.MATMULS:
        t = np.asarray(w[name]).reshape(w[name].shape[0], -1, 32,
                                        w[name].shape[2] // 32, 32)
        alive = np.abs(t).sum(axis=(2, 4)) > 0
        kept += int(alive.sum())
        total += alive.size
        np.testing.assert_array_equal(
            np.where(np.repeat(np.repeat(alive, 32, 1), 32, 2),
                     np.asarray(dense[name]), 0), np.asarray(w[name]))
    assert kept == total // 2


@pytest.mark.parametrize("cfg", [TINY, PRUNED], ids=["dense", "bsr"])
def test_engine_serves_the_reference_tokens(cfg):
    server = Server(cfg, "tiny", MIX, seed=9)
    if cfg["prune"]:
        assert server.tiles == {"total": 72, "kept": 36}
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, size=n, dtype=np.int32)
               for n in (9, 17, 24, 5)]
    rids = [server.submit(p, 14) for p in prompts]
    while server.busy():
        server.step()
    served = [(p, server.request(r).tokens) for p, r in zip(prompts, rids)]
    assert all(len(t) == 14 for _, t in served)
    w = check.reference_weights(cfg, 9)
    assert max(check.gaps(cfg, w, served, MIX["max_seq_len"])) < GAP
    first = server.engine.prefill_logits(prompts[1])
    want = _ref_logits(cfg, w, prompts[1])[-1]
    assert np.linalg.norm(first - want) / np.linalg.norm(want) < REL


def test_gap_reads_a_wrong_token():
    w = check.reference_weights(TINY, 9)
    prompt = np.arange(10, dtype=np.int32)
    logits = _ref_logits(TINY, w, prompt)
    best = int(np.argmax(logits[-1]))
    worst = int(np.argmin(logits[-1]))
    right = check.gaps(TINY, w, [(prompt, np.asarray([best]))], 48)[0]
    wrong = check.gaps(TINY, w, [(prompt, np.asarray([worst]))], 48)[0]
    assert right == 0.0
    assert wrong == pytest.approx(logits[-1].max() - logits[-1].min(), rel=1e-4)


def test_large_seeds_make_distinct_weights():
    a = model.weights(TINY, 2**31 + 1)["wq"]
    b = model.weights(TINY, 2**40 + 1)["wq"]
    c = model.weights(TINY, 2**31 + 1)["wq"]
    assert not np.array_equal(a, b) and np.array_equal(a, c)
