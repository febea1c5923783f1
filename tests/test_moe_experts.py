"""The held experts' grouped FFN kernel (kernels/moe_experts.py) and the
dropless serving layer on top of it (models/moe.moe_serve).

Kernel: the Pallas body under the interpreter against the jnp ref on
rows grouped by held expert, live rows only (dead tiles are unwritten
by the kernel).  Float32 throughout, so the two differ only in the order
of their sums: ~1e-6 relative on outputs of size ~1, well inside
``ATOL``.

Layer: every token's output is the gate-weighted sum of its routed held
experts' FFNs, computed from that token alone — checked against a plain
per-token loop, and by serving a token among different neighbours at
the same batch shape (bit-identical).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.moe_experts import moe_experts_pallas, moe_experts_ref
from repro.models.moe import moe_init, moe_serve

ATOL = 1e-5


def _grouped(rng, counts, tm, d, f):
    """x rows laid out by expert (each group padded to whole tiles of
    ``tm``), the per-tile expert map, live tile count, and weights."""
    e = len(counts)
    tiles = [-(-c // tm) for c in counts]
    live = sum(tiles)
    n_tiles = live + 2                                  # two dead tiles
    x = np.zeros((n_tiles * tm, d), np.float32)
    te = np.zeros((n_tiles,), np.int32)
    t = 0
    for ex, (c, nt) in enumerate(zip(counts, tiles)):
        x[t * tm:t * tm + c] = rng.normal(size=(c, d))
        te[t:t + nt] = ex
        t += nt
    te[live:] = te[live - 1] if live else 0
    w = [jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]), jnp.float32)
         for s in ((e, d, f), (e, d, f), (e, f, d))]
    return (jnp.asarray(x), *w, jnp.asarray(te),
            jnp.asarray([live], jnp.int32)), live


@pytest.mark.parametrize("counts", [
    [3, 0, 17, 1],          # an empty group between two others
    [0, 0, 40, 0],          # every row on one expert
    [8, 8, 8, 8],           # exactly whole tiles
    [0, 0, 0, 0],           # no row at all: every tile dead
], ids=["empty-group", "one-expert", "whole-tiles", "all-dead"])
def test_moe_experts_kernel_matches_ref(counts):
    rng = np.random.default_rng(sum(counts) + len(counts))
    tm, d, f = 8, 32, 48
    args, live = _grouped(rng, counts, tm, d, f)
    ref = np.asarray(moe_experts_ref(*args, block_rows=tm))
    ker = np.asarray(moe_experts_pallas(*args, block_rows=tm,
                                        interpret=True))
    rows = live * tm
    np.testing.assert_allclose(ker[:rows], ref[:rows], atol=ATOL)
    assert not ref[rows:].any()                 # dead tiles: zero on the ref
    # a live row is its own expert's FFN of its own input
    x, wg, wu, wd, te = (np.asarray(a) for a in args[:5])
    for r in range(0, rows, 5):
        e = te[r // tm]
        want = (jax.nn.silu(x[r] @ wg[e]) * (x[r] @ wu[e])) @ wd[e]
        np.testing.assert_allclose(ref[r], np.asarray(want), atol=ATOL)


def test_moe_experts_ops_mode_dispatch():
    rng = np.random.default_rng(3)
    args, live = _grouped(rng, [5, 0, 9], 8, 16, 32)
    ref = ops.moe_experts(*args, block_rows=8, mode="ref")
    itp = ops.moe_experts(*args, block_rows=8, mode="interpret")
    auto = ops.moe_experts(*args, block_rows=8)         # CPU host -> ref
    rows = live * 8
    np.testing.assert_allclose(np.asarray(itp)[:rows],
                               np.asarray(ref)[:rows], atol=ATOL)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


def _loop_moe(p, x, *, num_experts, top_k, held, offset):
    """Per-token plain loop over the routed experts that are held."""
    xt = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    router = np.asarray(p["router"]["kernel"], np.float64)
    out = np.zeros_like(xt)
    for t, row in enumerate(xt):
        logits = row @ router
        prob = np.exp(logits - logits.max())
        prob /= prob.sum()
        top = np.argsort(-prob, kind="stable")[:top_k]
        gates = prob[top] / prob[top].sum()
        for e, g in zip(top, gates):
            if not offset <= e < offset + held:
                continue
            le = e - offset
            h = row @ np.asarray(p["experts_gate"][le])
            h = h / (1 + np.exp(-h)) * (row @ np.asarray(p["experts_up"][le]))
            out[t] += g * (h @ np.asarray(p["experts_down"][le]))
    return out.reshape(x.shape)


@pytest.mark.parametrize("held,offset", [(None, 0), (4, 4), (2, 14)],
                         ids=["all-held", "middle-share", "last-share"])
def test_moe_serve_matches_a_per_token_loop(held, offset):
    e, k, d, f = 16, 4, 32, 24
    p = moe_init(jax.random.PRNGKey(0), d, f, e, held=held)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, d))
    y, stats = moe_serve(p, x, num_experts=e, top_k=k, held=held,
                         offset=offset)
    want = _loop_moe(p, x, num_experts=e, top_k=k, held=held or e,
                     offset=offset)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)
    assert 0 <= int(stats["touched"]) <= (held or e)
    assert int(stats["pairs"]) <= 3 * 7 * min(k, held or e)


def test_moe_serve_rows_are_independent():
    """Dropless: at the batch shape a decode step always has, a token's
    output is bit-identical among any other tokens at any row — here
    once among 23 distinct ones, once among 23 copies of one token that
    all pick the same experts (a capacity would drop some of them)."""
    e, k, d, f = 8, 2, 32, 16
    p = moe_init(jax.random.PRNGKey(2), d, f, e, held=4)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, d))
    kw = dict(num_experts=e, top_k=k, held=4, offset=2)
    many, _ = moe_serve(p, x, **kw)
    for t, row in ((0, 17), (5, 0), (23, 9)):
        crowd = jnp.repeat(x[:, 1:2] if t != 1 else x[:, 2:3], 24, axis=1)
        crowd = crowd.at[:, row].set(x[:, t])
        got, _ = moe_serve(p, crowd, **kw)
        np.testing.assert_array_equal(np.asarray(got[0, row]),
                                      np.asarray(many[0, t]))
