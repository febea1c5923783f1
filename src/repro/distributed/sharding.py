"""Logical-axis sharding: rules, constraints, and per-param PartitionSpecs.

Models annotate activations with *logical* axis names ("batch", "seq",
"embed", "heads", "mlp", "experts", "vocab", "kv_seq").  The launcher
installs a rule set mapping logical names to mesh axes; outside any rule
context the constraints are no-ops, so the same model code runs on one CPU
device in tests and on the 512-chip production mesh in the dry-run.

Parameter shardings are produced by path-pattern rules (Megatron TP on the
"model" axis + ZeRO-3/FSDP on the "data" axis), with divisibility-aware
fallbacks: a dim that does not divide its assigned mesh axes falls back to
replication on that axis (e.g. mixtral's 8 experts on a 16-way model axis
fall back to intra-expert TP — see DESIGN.md §4).
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any, Dict, Mapping, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import (AbstractMesh, AxisType, Mesh, NamedSharding,
                          PartitionSpec as P)

__all__ = [
    "axis_rules",
    "logical_constraint",
    "make_train_rules",
    "make_decode_rules",
    "param_pspecs",
    "named_sharding_tree",
    "current_rules",
    "make_mesh",
]

def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: logical constraints
    steer GSPMD, no axis is in explicit-sharding mode."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


AxisVal = Union[None, str, Tuple[str, ...]]

_RULES: contextvars.ContextVar[Optional[Dict[str, AxisVal]]] = contextvars.ContextVar(
    "repro_axis_rules", default=None
)


@contextlib.contextmanager
def axis_rules(rules: Optional[Mapping[str, AxisVal]]):
    token = _RULES.set(dict(rules) if rules is not None else None)
    try:
        yield
    finally:
        _RULES.reset(token)


def current_rules() -> Optional[Dict[str, AxisVal]]:
    return _RULES.get()


def _mesh_axis_size(mesh: Mesh, axis: AxisVal) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return int(np.prod([mesh.shape[a] for a in axis]))


def logical_constraint(x, *logical_axes: Optional[str]):
    """with_sharding_constraint by logical names; no-op without rules/mesh.

    Dims whose size does not divide the mapped mesh axes are left
    unconstrained (None) rather than failing.
    """
    rules = _RULES.get()
    if rules is None:
        return x
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    spec = []
    for dim, name in enumerate(logical_axes):
        axis = rules.get(name) if name is not None else None
        if axis is not None and x.shape[dim] % _mesh_axis_size(mesh, axis) != 0:
            axis = None
        spec.append(axis)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _ambient_mesh() -> Optional[AbstractMesh]:
    """The mesh installed by ``jax.set_mesh`` (visible inside jit), or
    None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def make_train_rules(multi_pod: bool) -> Dict[str, AxisVal]:
    dp = ("pod", "data") if multi_pod else ("data",)
    return {
        "batch": dp,
        "seq": None,
        "embed": None,
        "heads": "model",
        "kv": None,
        "mlp": "model",
        "experts": "model",   # EP weights (only when cfg.moe_ep)
        "expert_cap": "model", # MoE dispatch-buffer capacity dim
        "vocab": "model",
        "kv_seq": None,       # training: KV not sharded on seq
        "res_seq": "model",   # used only when cfg.seq_sharded_acts (SP)
        "fsdp": "data",
        "tp": "model",
    }


def make_decode_rules(multi_pod: bool, *, shard_cache_seq: bool) -> Dict[str, AxisVal]:
    """Decode: small batches; optionally context-parallel KV cache."""
    rules = make_train_rules(multi_pod)
    if shard_cache_seq:
        # batch=1 long-context: batch unshardable, cache seq over data
        rules["batch"] = None
        rules["kv_seq"] = "data"
        rules["seq"] = None
    else:
        rules["kv_seq"] = None
    return rules


# ---------------------------------------------------------------------------
# Parameter sharding
# ---------------------------------------------------------------------------

# (path regex, spec builder) — first match wins.  Spec builders receive the
# shape and mesh and return a PartitionSpec with divisibility fallbacks.
def _spec(shape, mesh, *axes: AxisVal) -> P:
    fixed = []
    for dim, axis in enumerate(axes):
        if axis is not None and shape[dim] % _mesh_axis_size(mesh, axis) != 0:
            axis = None
        fixed.append(axis)
    return P(*fixed)


def param_pspecs(
    shapes: Mapping[str, Any], mesh: Mesh, *, fsdp_axis: str = "data", tp_axis: str = "model"
):
    """PartitionSpec pytree for a params pytree of ShapeDtypeStructs/arrays.

    Patterns (matched on '/'-joined path):
      embedding (V, D)                   -> (tp, fsdp)     vocab-parallel
      attn q/o, mlp in/out, generic 2-D  -> col/row TP + FSDP
      moe experts (E, D, F)              -> EP on tp if divisible else
                                             intra-expert TP
      1-D (norm scales, biases)          -> replicated (tiny)
    """
    d, t = fsdp_axis, tp_axis

    def rule(path: str, shape: Tuple[int, ...]) -> P:
        n = len(shape)
        pl = path.lower()
        if n <= 1:
            return P()
        if re.search(r"(embed|tok_embeddings|lm_head|unembed)", pl):
            # (V, D) — vocab on TP axis, embed on FSDP
            return _spec(shape, mesh, t, d)
        if n == 3 and re.search(r"(expert|moe)", pl):
            # default: weights FSDP-sharded over data, replicated over model
            # (compute parallelism comes from the capacity dim — §Perf G2);
            # large-expert models (mixtral) TP the inner dims instead.
            e = shape[0]
            if e % _mesh_axis_size(mesh, t) != 0 or shape[1] * shape[2] >= 16_000_000:
                if re.search(r"(w_down|down|wo)", pl):
                    return _spec(shape, mesh, None, t, d)   # (E, F, D)
                return _spec(shape, mesh, None, d, t)       # (E, D, F)
            return _spec(shape, mesh, None, d, None)        # FSDP only
        if n == 2:
            if re.search(r"(wo|out_proj|o_proj|down|w2|dense_4h|proj_out)", pl):
                return _spec(shape, mesh, t, d)             # row-parallel
            return _spec(shape, mesh, d, t)                 # col-parallel
        if n == 3:
            # fused qkv (D, H, dh) or conv (kw, cin, cout)
            return _spec(shape, mesh, d, t, None)
        if n >= 4:
            return _spec(shape, mesh, *([None] * (n - 2)), d, t)
        return P()

    def walk(node, prefix):
        if isinstance(node, Mapping):
            return {k: walk(v, f"{prefix}/{k}" if prefix else str(k)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            out = [walk(v, f"{prefix}/{i}") for i, v in enumerate(node)]
            return type(node)(out) if isinstance(node, tuple) else out
        if node is None:
            return None
        return rule(prefix, tuple(node.shape))

    return walk(shapes, "")


def named_sharding_tree(pspecs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if s is not None else None,
        pspecs,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )
