"""Whether the served tokens are correct: a comparison with the plain
float32 reference of the configuration, and its control.

For each sampled request the reference runs once over the prompt and the
served tokens, and reads, at each position that produced a served token,
how far that token's logit lies below the reference's best logit.  The
widest such gap over the sample is compared with the cell's limit
(``bench/limits/<cell>.json``).  Greedy decoding serves the argmax of the
program's own logits, so a sound program reads a gap of the size of its
rounding only where two logits nearly tie.

The control puts the reference in the program's place at the next lower
precision than the configuration states -- every matmul's inputs in
float8 (e4m3, scaled per row and per output column) for a bfloat16
configuration -- and reads the gap of the token it puts first.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import model

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
BLOCK = 128          # logit rows computed at once


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def fp8_dot(x, w):
    """x @ w with x rounded to float8 per row and w per output column."""
    return jnp.dot(_q8(x, -1), _q8(w, 0), precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _gap(cfg_json: str, control: bool, w, tokens, lo, hi):
    cfg = json.loads(cfg_json)
    ref = model.reference(cfg)
    t = tokens.shape[0]
    x = ref.hidden(w, tokens, cfg)
    head = ref.head(w, cfg)
    picks = jnp.roll(tokens, -1)          # the token served after each row
    xc = ref.hidden(w, tokens, cfg, mm=fp8_dot) if control else x
    blocks = t // BLOCK

    def rows(args):
        xb, pb, xcb = args
        logits = jnp.dot(xb, head, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        if control:
            pb = jnp.argmax(fp8_dot(xcb, head), axis=-1)
        got = jnp.take_along_axis(logits, pb[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    g = jax.lax.map(rows, (x.reshape(blocks, BLOCK, -1),
                           picks.reshape(blocks, BLOCK),
                           xc.reshape(blocks, BLOCK, -1))).reshape(t)
    pos = jnp.arange(t)
    return jnp.max(jnp.where((pos >= lo) & (pos < hi), g, -jnp.inf))


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_json: str, control: bool):
    return jax.jit(functools.partial(_gap, cfg_json, control))


def reference_weights(cfg: Dict, seed: int) -> Dict[str, jnp.ndarray]:
    """The configuration's weights for ``seed``, as served, in float32."""
    w = model.weights(cfg, seed)
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def gaps(cfg: Dict, w, served: Sequence[Tuple[np.ndarray, np.ndarray]],
         length: int, control: bool = False) -> List[float]:
    """Widest logit gap of each (prompt, served tokens) pair.  Sequences
    are padded to ``length`` (rounded up to a whole block): padding after
    the last served token changes nothing before it under a causal mask."""
    length = -(-length // BLOCK) * BLOCK
    fn = _gap_fn(json.dumps(cfg, sort_keys=True), control)
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, toks in served:
            seq = np.zeros((length,), np.int32)
            n_p, n_t = len(prompt), len(toks)
            seq[:n_p] = prompt
            seq[n_p:n_p + n_t] = toks
            out.append(float(fn(w, jnp.asarray(seq), n_p - 1, n_p + n_t - 1)))
    return out


def sample(finished: Dict[int, int], n: int, seed: int) -> List[int]:
    """``n`` request ids from ``finished`` ({rid: tokens}), drawn from the
    seed, the one with the most tokens always among them."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (finished[r], -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed), 2])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]
