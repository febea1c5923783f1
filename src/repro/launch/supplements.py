"""Roofline supplements for scanned loop bodies (see roofline.py docstring).

XLA cost analysis counts a while-loop body once.  Our models unroll chunk
loops up to ``CHUNK_UNROLL_LIMIT`` chunks; beyond that (and for the
inherently sequential sLSTM time loop) the loop body is compiled standalone
here and its costs are added (trips-1) times.

Accounting conventions (documented approximations):
* train cells multiply body cost x3 (fwd+bwd ~= 3x fwd);
* body costs are divided by the model-axis size (the body's wide dims are
  TP-sharded in the real program);
* per-device batch = global_batch / dp_size.
Only scan-bound archs (xlstm sLSTM; jamba/xlstm long-sequence chunk scans)
have non-zero supplements.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import mamba as mamba_mod
from repro.models import xlstm as xlstm_mod
from repro.models.transformer import layer_specs

__all__ = ["supplements_for"]


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _body_cost(fn, args) -> Tuple[float, float]:
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def supplements_for(
    cfg: ModelConfig, cell: ShapeCell, *, model_size: int, dp_size: int
) -> Dict[str, float]:
    if cell.kind == "decode":
        return {}
    s = cell.seq_len
    b = max(cell.global_batch // max(dp_size, 1), 1)
    train_mult = 3.0 if cell.kind == "train" else 1.0

    specs = layer_specs(cfg)
    n_slstm = sum(1 for sp in specs if sp.mixer == "slstm")
    n_mamba = sum(1 for sp in specs if sp.mixer == "mamba")
    n_mlstm = sum(1 for sp in specs if sp.mixer == "mlstm")

    flops = 0.0
    byts = 0.0
    detail: Dict[str, float] = {}

    # --- sLSTM time scan (always sequential) --------------------------------
    if n_slstm:
        d = cfg.d_model
        h = cfg.n_heads
        dh = d // h
        state = tuple(_sds((b, d)) for _ in range(4))

        def slstm_body(state, wx, r):
            return xlstm_mod._slstm_step(state, wx, r, h)

        f, by = _body_cost(
            slstm_body, (state, _sds((b, 4 * d)), _sds((h, dh, 4 * dh), cfg.dtype))
        )
        trips = (s - 1) * n_slstm
        flops += f * trips * train_mult
        byts += by * trips * train_mult
        detail["slstm_body_flops"] = f
        detail["slstm_trips"] = trips

    # --- mamba chunk scan (only past the unroll limit) -----------------------
    chunk = min(cfg.ssm_chunk, s)
    n_chunks = -(-s // chunk)
    scanned_ssm = n_chunks > mamba_mod.CHUNK_UNROLL_LIMIT and s % chunk == 0
    if n_mamba and scanned_ssm:
        di = 2 * cfg.d_model
        n = cfg.d_state
        dtr = max(cfg.d_model // 16, 1)
        p_spec = {
            "x_proj": {"kernel": _sds((di, dtr + 2 * n), cfg.dtype)},
            "dt_proj": {"kernel": _sds((dtr, di), cfg.dtype),
                        "bias": _sds((di,), cfg.dtype)},
        }

        def mamba_body(p, hc, xc, a):
            xcf = xc.astype(jnp.float32)
            dt, bm, cm = mamba_mod._ssm_params(p, xc)
            y, hn = mamba_mod._ssm_chunk(hc, dt, bm, cm, xcf, a)
            return hn, y

        f, by = _body_cost(
            mamba_body,
            (p_spec, _sds((b, di, n)), _sds((b, chunk, di), cfg.dtype), _sds((di, n))),
        )
        trips = (n_chunks - 1) * n_mamba
        flops += f * trips * train_mult
        byts += by * trips * train_mult
        detail["mamba_body_flops"] = f
        detail["mamba_trips"] = trips

    # --- mLSTM chunk scan -----------------------------------------------------
    scanned_mlstm = n_chunks > xlstm_mod.CHUNK_UNROLL_LIMIT and s % chunk == 0
    if n_mlstm and scanned_mlstm:
        d_in = int(cfg.mlstm_proj_factor * cfg.d_model)
        d_in -= d_in % cfg.n_heads
        h = cfg.n_heads
        dh = d_in // h
        carry = (_sds((b, h, dh, dh)), _sds((b, h, dh)), _sds((b, h)))
        qkv = _sds((b, h, chunk, dh))
        gate = _sds((b, h, chunk))
        f, by = _body_cost(
            xlstm_mod._mlstm_chunk, (carry, qkv, qkv, qkv, gate, gate)
        )
        trips = (n_chunks - 1) * n_mlstm
        flops += f * trips * train_mult
        byts += by * trips * train_mult
        detail["mlstm_body_flops"] = f
        detail["mlstm_trips"] = trips

    if flops == 0.0:
        return {}
    out = {"flops": flops / model_size, "bytes": byts / model_size}
    out.update(detail)
    return out
