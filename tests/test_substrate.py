"""Optimizer, checkpoint, data-pipeline, trainer fault-tolerance tests."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.data import LMPipeline, TokenTask
from repro.optim import (
    AdamWConfig,
    adamw_update,
    clip_by_global_norm,
    constant_lr,
    init_opt_state,
    warmup_cosine,
)
from repro.train import Trainer, TrainerConfig


def test_adamw_masked_updates_keep_zeros():
    params = {"w": jnp.ones((4, 4))}
    masks = {"w": jnp.asarray(np.tril(np.ones((4, 4), np.float32)))}
    cfg = AdamWConfig(use_master=True, weight_decay=0.1)
    opt = init_opt_state(params, cfg)
    params = {"w": params["w"] * masks["w"]}
    for _ in range(5):
        grads = {"w": jnp.ones((4, 4))}
        params, opt = adamw_update(params, grads, opt, cfg, jnp.asarray(0.1), masks=masks)
    w = np.asarray(params["w"])
    assert np.all(w[np.triu_indices(4, 1)] == 0), "pruned weights drifted"
    assert np.all(w[np.tril_indices(4)] != 1.0), "unpruned weights must move"


def test_grad_clip():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    flat = np.asarray(clipped["a"])
    assert np.linalg.norm(flat) <= 1.0 + 1e-5


def test_warmup_cosine_shape():
    sched = warmup_cosine(1.0, 10, 100)
    assert float(sched(jnp.asarray(0))) == 0.0
    assert float(sched(jnp.asarray(10))) == pytest.approx(1.0, abs=0.1)
    assert float(sched(jnp.asarray(100))) == pytest.approx(0.1, abs=0.05)


def test_checkpoint_atomicity_and_gc():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        state = {"w": jnp.arange(6.0), "n": {"m": jnp.zeros((2, 2))}}
        for s in (1, 5, 9):
            ck.save(s, state)
        assert ck.committed_steps() == [5, 9]
        # a stale tmp dir must not be treated as a checkpoint
        os.makedirs(os.path.join(d, "step_0000000011.tmp"))
        assert ck.latest_step() == 9
        out = ck.restore(target=state)
        np.testing.assert_allclose(out["w"], state["w"])


def test_checkpoint_elastic_restore_shapes():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        state = {"w": jnp.arange(8.0).reshape(2, 4)}
        ck.save(3, state)
        out = ck.restore(target=state, shardings={"w": None})
        assert out["w"].shape == (2, 4)


def test_pipeline_determinism_and_prefetch():
    task = TokenTask(vocab=97)
    pipe = LMPipeline(task, batch=4, seq=32, prefetch=2)
    a = pipe.batch_at(7)
    b = pipe.batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    seen = list(pipe.run(0, 3))
    assert len(seen) == 3
    np.testing.assert_array_equal(np.asarray(seen[1]["tokens"]),
                                  np.asarray(pipe.batch_at(1)["tokens"]))


def _tiny_step():
    def step(state, batch):
        loss = jnp.mean((state["w"] - batch["x"]) ** 2)
        state = {"w": state["w"] - 0.1 * (state["w"] - jnp.mean(batch["x"])),
                 "step": state["step"] + 1}
        return state, {"total_loss": loss}
    return step


def test_trainer_resume_after_interrupt():
    with tempfile.TemporaryDirectory() as d:
        import dataclasses as _dc

        cfg5 = TrainerConfig(total_steps=5, ckpt_every=5, ckpt_dir=d, log_every=1)
        batch_fn = lambda s: {"x": jnp.full((4,), float(s))}
        state = {"w": jnp.zeros(()), "step": jnp.asarray(0)}

        t1 = Trainer(_tiny_step(), state, batch_fn, cfg5)  # dies at step 5
        r1 = t1.run()
        assert r1["final_step"] == 5

        cfg10 = _dc.replace(cfg5, total_steps=10)
        t2 = Trainer(_tiny_step(), state, batch_fn, cfg10)
        start = t2.resume_if_available()
        assert start == 5, "must resume from the committed checkpoint"
        r2 = t2.run()
        assert r2["final_step"] == 10


def test_trainer_straggler_detection():
    import time

    with tempfile.TemporaryDirectory() as d:
        cfg = TrainerConfig(total_steps=8, ckpt_every=0, ckpt_dir=d,
                            log_every=0, straggler_factor=3.0, ewma_alpha=0.5)
        slow = {5}

        def batch_fn(s):
            if s in slow:
                time.sleep(0.25)
            return {"x": jnp.ones((2,))}

        state = {"w": jnp.zeros(()), "step": jnp.asarray(0)}
        t = Trainer(_tiny_step(), state, batch_fn, cfg)
        r = t.run()
        assert any(e["step"] == 5 for e in r["stragglers"]), r["stragglers"]


def test_compression_error_feedback_converges():
    """Accumulated int8 psum with error feedback is unbiased over steps."""
    import os
    from repro.distributed.sharding import make_mesh
    from repro.optim.compression import compressed_psum

    # single-device: emulate via shard_map on a 1-axis mesh of size 1
    mesh = make_mesh((1,), ("pod",))
    from jax.sharding import PartitionSpec as P

    g = jnp.asarray(np.random.default_rng(0).normal(size=(64,)).astype(np.float32))
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    with jax.set_mesh(mesh):
        fn = jax.shard_map(
            lambda a, b: compressed_psum(a, b, "pod"),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)
        for _ in range(50):
            out, err = fn(g, err)
            total = total + out
    # mean of 50 compressed reductions ~= g (error feedback cancels bias)
    np.testing.assert_allclose(np.asarray(total / 50), np.asarray(g), atol=1e-3)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set and no other path is
    used; otherwise the cache goes to one fixed, git-ignored directory at
    the root of the checkout."""
    from pathlib import Path

    from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                            enable_compile_cache)

    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if from_env:
        assert path == str(tmp_path)
        return
    root = Path(__file__).resolve().parents[1]
    assert path == str(CHECKOUT_CACHE_DIR) == str(root / ".jax_cache")
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
