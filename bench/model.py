"""Weights and references of a configuration, found by name.

A configuration file (``bench/configs/<name>.json``) names its
``architecture``; ``bench/references/<architecture>.py`` is the plain
float32 forward of that architecture, and
``bench/adapters/<architecture>.py`` maps its sizes and weights onto the
program under test.  Weights are drawn from the seed on the device in one
jitted call, in the type they are served in.
"""
from __future__ import annotations

import functools
import importlib
import json
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import prune

BENCH = Path(__file__).resolve().parent


def load_config(name: str) -> Dict:
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def reference(cfg: Dict):
    return importlib.import_module(f"bench.references.{cfg['architecture']}")


def adapter(cfg: Dict):
    return importlib.import_module(f"bench.adapters.{cfg['architecture']}")


def weight_key(seed: int) -> jnp.ndarray:
    """A PRNG key from any non-negative whole number, however large."""
    state = np.random.SeedSequence([int(seed), 0]).generate_state(2, np.uint32)
    return jnp.asarray(state, jnp.uint32)


def _make(cfg_json: str, dtype: str, key):
    cfg = json.loads(cfg_json)
    ref = reference(cfg)
    w = ref.init_weights(cfg, key, jnp.dtype(dtype))
    p = cfg.get("prune")
    if p:
        # the tiles are chosen on the weights of the configuration's own
        # selection seed, so every run's seed prunes the same tiles and
        # the packed shapes (and so the compiled programs) never change
        chooser = ref.init_weights(cfg, weight_key(p["selection_seed"]),
                                   jnp.dtype(dtype))
        keep = prune.tile_keep(chooser, ref.MATMULS, p["block"],
                               p["sparsity"])
        w = prune.apply_keep(w, keep, p["block"])
    return w


@functools.lru_cache(maxsize=None)
def _maker(cfg_json: str, dtype: str):
    return jax.jit(functools.partial(_make, cfg_json, dtype))


def weights(cfg: Dict, seed: int, dtype: str = None) -> Dict[str, jnp.ndarray]:
    """The configuration's weights for ``seed`` in the reference's stacked
    layout, pruned where the configuration says so.  ``dtype`` defaults
    to the configuration's own (``torch_dtype``).  One compiled program
    makes them, so the same seed gives bit-identical values every time:
    the program under test and the reference get the same weights and
    the same pruned tiles."""
    dtype = dtype or cfg["torch_dtype"]
    return _maker(json.dumps(cfg, sort_keys=True), dtype)(weight_key(seed))
