"""Composable decoder / encoder-decoder stack covering all assigned archs.

A model is a list of ``LayerSpec``s (mixer + mlp per layer) generated from
``ModelConfig`` patterns:

  dense LM        mixer=attn,  mlp=dense
  MoE LM          mixer=attn,  mlp=moe
  jamba           mixer cycles mamba/attn (7:1), mlp cycles dense/moe
  xlstm           mixer cycles mlstm/slstm (7:1), mlp=none
  whisper         encoder (bidir attn+dense) + decoder (causal+cross+dense)
  qwen2-vl        dense LM + M-RoPE + patch-embed stub

Layers are python-unrolled (accurate XLA cost analysis; DESIGN.md §4) and
optionally rematerialized per layer.

Sparse execution: ``lm_forward`` and ``lm_decode`` accept params whose
matmul kernels were packed to BSR by ``repro.sparse.pack_params`` — every
matmul routes through the ``layers.matmul`` dispatch point, so pruned
tiles are skipped on both the prefill and the KV-cache decode paths
(DESIGN.md §6).  Packed leaves are registered pytrees: jit, remat and the
cache mechanics are oblivious to them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RopeSpec
from repro.distributed.sharding import logical_constraint
from . import attention as attn_mod
from . import mamba as mamba_mod
from . import xlstm as xlstm_mod
from .attention import (
    attention_apply,
    attention_decode,
    attention_init,
    attention_prefill,
    cross_attention_prefill,
    init_kv_cache,
)
from .ffn import mlp_apply, mlp_init
from .layers import (
    dense,
    dense_init,
    embed_init,
    embed_lookup,
    layernorm,
    layernorm_init,
    rmsnorm,
    rmsnorm_init,
    sinusoidal_positions,
    unembed_logits,
)
from .mamba import (
    init_mamba_cache,
    mamba_apply,
    mamba_decode,
    mamba_init,
    mamba_prefill,
)
from .moe import moe_apply, moe_init, moe_serve
from .moe_alltoall import alltoall_available, moe_alltoall_apply
from .xlstm import (
    init_mlstm_cache,
    init_slstm_cache,
    mlstm_apply,
    mlstm_decode,
    mlstm_init,
    mlstm_prefill,
    slstm_apply,
    slstm_decode,
    slstm_init,
    slstm_prefill,
)

__all__ = [
    "LayerSpec", "layer_specs", "init_params", "lm_forward", "lm_decode",
    "lm_prefill", "lm_generate", "init_caches", "encoder_forward",
    "encode_kv_caches", "cross_entropy_loss",
]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str                 # attn | mamba | mlstm | slstm | none
    mlp: str                   # dense | moe | none
    cross_attn: bool = False
    causal: bool = True
    use_rope: bool = True
    window: Optional[int] = None       # sliding-window size; None: full
    rope: Optional[RopeSpec] = None    # None: default RoPE at cfg.rope_theta


ATTN_KINDS = ("sliding", "full")


def layer_specs(cfg: ModelConfig) -> List[LayerSpec]:
    """One spec per layer, patterns cycled.  An attention layer's kind
    (``cfg.attn_kinds``) gives its window — ``cfg.window`` for
    "sliding", None for "full" — and full layers take ``cfg.rope_full``
    where the config sets one."""
    mix = cfg.mixer_pattern or ("attn",)
    mlp = cfg.mlp_pattern or ("dense",)
    kinds = cfg.attn_kinds or (("sliding",) if cfg.window else ("full",))
    if set(kinds) - set(ATTN_KINDS):
        raise ValueError(f"attention kinds {kinds} not in {ATTN_KINDS}")
    if "sliding" in kinds and not cfg.window:
        raise ValueError("sliding attention layers need cfg.window")
    specs = []
    for i in range(cfg.n_layers):
        kind = kinds[i % len(kinds)]
        specs.append(LayerSpec(
            mixer=mix[i % len(mix)],
            mlp=mlp[i % len(mlp)],
            cross_attn=False,
            causal=True,
            use_rope=cfg.use_rope,
            window=cfg.window if kind == "sliding" else None,
            rope=cfg.rope_full if kind == "full" else None,
        ))
    return specs


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _accum(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.row_accum_dtype == "bfloat16" else jnp.float32


def _out_seq(cfg: ModelConfig) -> str:
    return "res_seq" if cfg.seq_sharded_acts else "seq"


def _residual(cfg: ModelConfig, x):
    """Megatron-SP: residual stream sharded on seq over the TP axis when
    cfg.seq_sharded_acts — converts the per-layer TP all-reduces into
    all-gather + reduce-scatter pairs (half the wire bytes) and shrinks
    every residual/norm op 16x (EXPERIMENTS.md §Perf)."""
    if cfg.seq_sharded_acts:
        return logical_constraint(x, "batch", "res_seq", "embed")
    return x


def _norm_init(cfg: ModelConfig):
    return layernorm_init(cfg.d_model, cfg.dtype) if cfg.norm_type == "layernorm" \
        else rmsnorm_init(cfg.d_model, cfg.dtype)


def _norm_apply(cfg: ModelConfig, p, x):
    return layernorm(p, x) if cfg.norm_type == "layernorm" else rmsnorm(p, x)


def _init_mixer(key, spec: LayerSpec, cfg: ModelConfig) -> Dict:
    if spec.mixer == "attn":
        p = {
            "attn": attention_init(
                key, cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim_(),
                qkv_bias=cfg.qkv_bias, dtype=cfg.dtype,
            )
        }
        if spec.cross_attn:
            k2 = jax.random.fold_in(key, 1)
            p["cross"] = attention_init(
                k2, cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim_(),
                qkv_bias=cfg.qkv_bias, dtype=cfg.dtype,
            )
            p["cross_norm"] = _norm_init(cfg)
        return p
    if spec.mixer == "mamba":
        return {"mamba": mamba_init(
            key, cfg.d_model, d_state=cfg.d_state, d_conv=cfg.d_conv, dtype=cfg.dtype)}
    if spec.mixer == "mlstm":
        return {"mlstm": mlstm_init(
            key, cfg.d_model, cfg.n_heads, proj_factor=cfg.mlstm_proj_factor, dtype=cfg.dtype)}
    if spec.mixer == "slstm":
        return {"slstm": slstm_init(key, cfg.d_model, cfg.n_heads, dtype=cfg.dtype)}
    if spec.mixer == "none":
        return {}
    raise ValueError(f"unknown mixer {spec.mixer}")


def _init_mlp(key, spec: LayerSpec, cfg: ModelConfig) -> Dict:
    if spec.mlp == "dense":
        return {"mlp": mlp_init(
            key, cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp, dtype=cfg.dtype)}
    if spec.mlp == "moe":
        return {"moe": moe_init(
            key, cfg.d_model, cfg.d_ff, cfg.moe_experts, gated=cfg.gated_mlp,
            dtype=cfg.dtype, held=cfg.experts_held)}
    if spec.mlp == "none":
        return {}
    raise ValueError(f"unknown mlp {spec.mlp}")


def _init_layer(key, spec: LayerSpec, cfg: ModelConfig) -> Dict:
    km, kf = jax.random.split(key)
    p: Dict[str, Any] = {"pre_norm": _norm_init(cfg)}
    p.update(_init_mixer(km, spec, cfg))
    if spec.mlp != "none":
        p["post_norm"] = _norm_init(cfg)
        p.update(_init_mlp(kf, spec, cfg))
    return p


def init_params(key, cfg: ModelConfig) -> Dict:
    keys = jax.random.split(key, cfg.n_layers + 4)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab, cfg.d_model, cfg.dtype),
        "layers": [
            _init_layer(keys[2 + i], spec, cfg)
            for i, spec in enumerate(layer_specs(cfg))
        ],
        "final_norm": _norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[1], cfg.vocab, cfg.d_model, cfg.dtype)
    if cfg.enc_layers > 0:  # encoder-decoder (whisper)
        ekeys = jax.random.split(keys[-1], cfg.enc_layers + 1)
        enc_spec = LayerSpec(mixer="attn", mlp="dense", causal=False, use_rope=False)
        params["encoder"] = {
            "layers": [_init_layer(ekeys[i], enc_spec, cfg) for i in range(cfg.enc_layers)],
            "final_norm": _norm_init(cfg),
        }
        # decoder layers gain cross-attention
        dec_spec = LayerSpec(mixer="attn", mlp="dense", cross_attn=True,
                             use_rope=cfg.use_rope)
        params["layers"] = [
            _init_layer(keys[2 + i], dec_spec, cfg) for i in range(cfg.n_layers)
        ]
    return params


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def _apply_mixer(
    p: Dict, spec: LayerSpec, cfg: ModelConfig, x: jnp.ndarray,
    positions, enc_out: Optional[jnp.ndarray],
    raw_x: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    if spec.mixer == "attn":
        h = attention_apply(
            p["attn"], x,
            num_heads=cfg.n_heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim_(),
            positions=positions, causal=spec.causal, window=spec.window,
            chunk=cfg.attn_chunk, rope_theta=cfg.rope_theta, rope=spec.rope,
            mrope_sections=cfg.mrope_sections, use_rope=spec.use_rope,
            accum=_accum(cfg), out_seq=_out_seq(cfg),
        )
        if spec.cross_attn and enc_out is not None:
            # cross-attn reads the RAW residual + self-attn output (the
            # whisper pre-norm dataflow, and what the decode path does) —
            # not the pre-normed x this function received
            base = raw_x if raw_x is not None else x
            xc = _norm_apply(cfg, p["cross_norm"], base + h)
            hc = attention_apply(
                p["cross"], xc,
                num_heads=cfg.n_heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim_(),
                causal=False, chunk=cfg.attn_chunk, kv_input=enc_out, use_rope=False,
            )
            h = h + hc
        return h
    if spec.mixer == "mamba":
        return mamba_apply(p["mamba"], x, chunk=cfg.ssm_chunk)
    if spec.mixer == "mlstm":
        return mlstm_apply(p["mlstm"], x, num_heads=cfg.n_heads, chunk=cfg.ssm_chunk)
    if spec.mixer == "slstm":
        return slstm_apply(p["slstm"], x, num_heads=cfg.n_heads)
    if spec.mixer == "none":
        return jnp.zeros_like(x)
    raise ValueError(spec.mixer)


def _apply_layer(
    p: Dict, spec: LayerSpec, cfg: ModelConfig, x: jnp.ndarray,
    positions, enc_out,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pre-norm residual layer. Returns (x, moe_aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = _apply_mixer(p, spec, cfg, _norm_apply(cfg, p["pre_norm"], x),
                     positions, enc_out, raw_x=x)
    x = _residual(cfg, x + h)
    if spec.mlp == "dense":
        # the residual rides the w_down epilogue (fused on packed params)
        x = _residual(cfg, mlp_apply(
            p["mlp"], _norm_apply(cfg, p["post_norm"], x),
            activation=cfg.activation, accum=_accum(cfg),
            out_seq=_out_seq(cfg), residual=x))
    elif spec.mlp == "moe":
        xn = _norm_apply(cfg, p["post_norm"], x)
        if cfg.moe_experts_held is not None:
            # one chip's share of the experts: only the dropless layer
            # computes a share
            y, _ = _moe_serve(cfg, p["moe"], xn)
        elif cfg.moe_impl == "alltoall" and alltoall_available(cfg.moe_experts):
            y, aux = moe_alltoall_apply(
                p["moe"], xn,
                num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.capacity_factor, activation=cfg.activation,
            )
        else:
            y, aux = moe_apply(
                p["moe"], xn,
                num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.capacity_factor, activation=cfg.activation,
            )
        x = _residual(cfg, x + y)
    return x, aux


def _moe_serve(cfg: ModelConfig, p: Dict, x: jnp.ndarray):
    return moe_serve(p, x, num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                     held=cfg.moe_experts_held, offset=cfg.moe_expert_offset,
                     activation=cfg.activation)


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy, static_argnums=())


def encoder_forward(params: Dict, frames: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend, per assignment).  frames (B, T, D)."""
    x = frames.astype(cfg.adtype)
    pos = sinusoidal_positions(frames.shape[1], cfg.d_model).astype(cfg.adtype)
    x = x + pos[None]
    spec = LayerSpec(mixer="attn", mlp="dense", causal=False, use_rope=False)
    for lp in params["encoder"]["layers"]:
        fn = _remat_wrap(
            lambda p, y: _apply_layer(p, spec, cfg, y, None, None)[0], cfg)
        x = fn(lp, x)
    return _norm_apply(cfg, params["encoder"]["final_norm"], x)


def lm_forward(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Forward to fp32 logits.  batch keys: tokens (B,S) [, positions,
    patch_embeds, frames]."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, dtype=cfg.adtype)

    if cfg.num_patches > 0 and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cfg.adtype)     # (B, P, D)
        x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)

    positions = batch.get("positions")
    if positions is None:
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :, None], (b, s, 3))
        else:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    enc_out = None
    if cfg.enc_layers > 0:
        enc_out = encoder_forward(params, batch["frames"], cfg)

    x = logical_constraint(x, "batch", "seq", "embed")
    aux_total = jnp.zeros((), jnp.float32)
    specs = layer_specs(cfg) if cfg.enc_layers == 0 else [
        LayerSpec(mixer="attn", mlp="dense", cross_attn=True, use_rope=cfg.use_rope)
    ] * cfg.n_layers
    for lp, spec in zip(params["layers"], specs):
        fn = _remat_wrap(
            functools.partial(_apply_layer, spec=spec, cfg=cfg), cfg)
        x, aux = fn(lp, x=x, positions=positions, enc_out=enc_out)
        aux_total = aux_total + aux

    x = _norm_apply(cfg, params["final_norm"], x)
    head = params.get("lm_head", params["embed"])
    logits = unembed_logits(head, x)
    if cfg.logits_softcap:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits, {"moe_aux": aux_total}


def cross_entropy_loss(
    logits: jnp.ndarray, labels: jnp.ndarray, *, z_loss: float = 1e-4
) -> jnp.ndarray:
    """Token-mean xent over vocab-sharded fp32 logits + z-loss.

    The label logit is extracted with a one-hot reduction, NOT
    take_along_axis: a gather over the vocab-sharded dim would all-gather
    the full logits (10 GB/step/device at qwen scale — measured in §Perf);
    the one-hot multiply-reduce keeps the vocab dim sharded and lowers to a
    partial sum + tiny all-reduce."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = labels[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, logits.shape[-1]), 2
    )
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    loss = jnp.mean(lse - ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss


# ---------------------------------------------------------------------------
# Decode (serve path)
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
                ) -> List[Dict]:
    caches: List[Dict] = []
    specs = layer_specs(cfg)
    for spec in specs:
        if cfg.enc_layers > 0:
            spec = LayerSpec(mixer="attn", mlp="dense", cross_attn=True,
                             use_rope=cfg.use_rope)
        if spec.mixer == "attn":
            alloc = max_len if spec.window is None else min(max_len, spec.window)
            c = init_kv_cache(batch, alloc, cfg.kv_heads, cfg.head_dim_(), dtype)
            if cfg.enc_layers > 0:
                c["cross_k"] = jnp.zeros(
                    (batch, cfg.enc_frames, cfg.kv_heads, cfg.head_dim_()), dtype)
                c["cross_v"] = jnp.zeros_like(c["cross_k"])
            caches.append(c)
        elif spec.mixer == "mamba":
            caches.append(init_mamba_cache(batch, 2 * cfg.d_model, cfg.d_state,
                                           cfg.d_conv, dtype))
        elif spec.mixer == "mlstm":
            d_in = int(cfg.mlstm_proj_factor * cfg.d_model)
            d_in -= d_in % cfg.n_heads
            caches.append(init_mlstm_cache(batch, cfg.n_heads, d_in // cfg.n_heads))
        elif spec.mixer == "slstm":
            caches.append(init_slstm_cache(batch, cfg.d_model))
        else:
            caches.append({})
    return caches


def encode_kv_caches(params: Dict, enc_out: jnp.ndarray, cfg: ModelConfig,
                     caches: List[Dict]) -> List[Dict]:
    """Precompute encoder K/V for decoder cross-attention (whisper)."""
    from .attention import _split_heads  # local: private helper

    for lp, c in zip(params["layers"], caches):
        k = _split_heads(dense(lp["cross"]["wk"], enc_out), cfg.kv_heads)
        v = _split_heads(dense(lp["cross"]["wv"], enc_out), cfg.kv_heads)
        c["cross_k"] = k.astype(c["cross_k"].dtype)
        c["cross_v"] = v.astype(c["cross_v"].dtype)
    return caches


def lm_decode(
    params: Dict,
    caches: List[Dict],
    batch: Dict[str, jnp.ndarray],
    cache_len: jnp.ndarray,
    cfg: ModelConfig,
    *,
    moe_stats: bool = False,
):
    """One-token decode. batch["tokens"] (B, 1). Returns (logits, caches),
    and with ``moe_stats`` a third value: the MoE layers' routed
    (token, held expert) pairs and held experts touched, summed over
    layers (int32 scalars).

    ``cache_len`` is a scalar or per-row ``(B,)`` vector (ragged prompts).
    With ``batch["page_tables"]`` (B, max_pages) the attention caches are
    page pools — ``(num_pages, K, page_size, dh)`` — and every self-attn
    layer reads/writes through the tables (DESIGN.md §9)."""
    tokens = batch["tokens"]
    b = tokens.shape[0]
    page_tables = batch.get("page_tables")
    x = embed_lookup(params["embed"], tokens, dtype=cfg.adtype)
    x = logical_constraint(x, "batch", None, "embed")

    specs = layer_specs(cfg)
    if cfg.enc_layers > 0:
        specs = [LayerSpec(mixer="attn", mlp="dense", cross_attn=True,
                           use_rope=cfg.use_rope)] * cfg.n_layers

    new_caches: List[Dict] = []
    stats = {"pairs": jnp.zeros((), jnp.int32),
             "touched": jnp.zeros((), jnp.int32)}
    for lp, spec, cache in zip(params["layers"], specs, caches):
        h_in = _norm_apply(cfg, lp["pre_norm"], x)
        if spec.mixer == "attn":
            h, cache2 = attention_decode(
                lp["attn"], h_in, {"k": cache["k"], "v": cache["v"]}, cache_len,
                num_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                head_dim=cfg.head_dim_(), window=spec.window,
                rope_theta=cfg.rope_theta, rope=spec.rope,
                mrope_sections=cfg.mrope_sections,
                use_rope=spec.use_rope, page_table=page_tables,
                paged_impl=cfg.paged_attn_impl,
            )
            cache = {**cache, **cache2}
            if spec.cross_attn:
                xc = _norm_apply(cfg, lp["cross_norm"], x + h)
                enc_len = jnp.asarray(cache["cross_k"].shape[1], jnp.int32)
                hc, _ = attention_decode(
                    lp["cross"], xc,
                    {"k": cache["cross_k"], "v": cache["cross_v"]}, enc_len,
                    num_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                    head_dim=cfg.head_dim_(), update_cache=False,
                )
                h = h + hc
        elif spec.mixer == "mamba":
            h, cache = mamba_decode(lp["mamba"], h_in, cache)
        elif spec.mixer == "mlstm":
            h, cache = mlstm_decode(lp["mlstm"], h_in, cache, num_heads=cfg.n_heads)
        elif spec.mixer == "slstm":
            h, cache = slstm_decode(lp["slstm"], h_in, cache, num_heads=cfg.n_heads)
        else:
            h = jnp.zeros_like(x)
        x = x + h
        if spec.mlp == "dense":
            x = mlp_apply(lp["mlp"], _norm_apply(cfg, lp["post_norm"], x),
                          activation=cfg.activation, residual=x)
        elif spec.mlp == "moe":
            y, st = _moe_serve(cfg, lp["moe"],
                               _norm_apply(cfg, lp["post_norm"], x))
            stats = {k: stats[k] + st[k] for k in stats}
            x = x + y
        new_caches.append(cache)

    x = _norm_apply(cfg, params["final_norm"], x)
    head = params.get("lm_head", params["embed"])
    logits = unembed_logits(head, x)
    if cfg.logits_softcap:
        # keep decode logits consistent with lm_forward/lm_prefill —
        # sampling inside lm_generate sees the same capped distribution
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    if moe_stats:
        return logits, new_caches, stats
    return logits, new_caches


# ---------------------------------------------------------------------------
# Serving hot path: batched prefill + on-device decode loop (DESIGN.md §7)
# ---------------------------------------------------------------------------

def lm_prefill(
    params: Dict,
    caches: List[Dict],
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    *,
    start_pos: int = 0,
) -> Tuple[jnp.ndarray, List[Dict]]:
    """Cache-filling batched prefill: one `lm_forward`-style pass over the
    whole prompt that also fills every KV/SSM cache, replacing
    ``prompt_len`` sequential decode steps.  batch["tokens"] (B, S).
    Returns (fp32 logits (B, S, V), caches ready for ``cache_len=S``).

    With ``batch["page_tables"]`` (B, max_pages) the attention caches
    are page pools — ``(num_pages, K, page_size, dh)`` — and every
    self-attn layer scatters its prompt K/V straight into the pages the
    rows own (paged prefill, DESIGN.md §10); recurrent and cross-attn
    caches are unaffected.

    ``start_pos`` (static, paged-only) runs a *tail-only* prefill for a
    prefix-cache hit (DESIGN.md §12): ``batch["tokens"]`` holds only the
    uncached suffix, which sits at logical positions
    ``[start_pos, start_pos+S)``; the first ``start_pos`` tokens' K/V
    already live in shared prefix pages mapped into the rows' tables.
    Attention-only stacks only — a recurrent mixer's state cannot be
    resumed from pages it never saw.

    Runs unchanged on packed (BSR) params — every matmul routes through
    the ``layers.matmul`` / ``layers.expert_matmul`` dispatch points."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    page_tables = batch.get("page_tables")
    x = embed_lookup(params["embed"], tokens, dtype=cfg.adtype)

    if cfg.num_patches > 0 and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cfg.adtype)
        x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)

    positions = batch.get("positions")
    if positions is None:
        pos1 = jnp.arange(start_pos, start_pos + s)
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(pos1[None, :, None], (b, s, 3))
        else:
            positions = jnp.broadcast_to(pos1[None], (b, s))

    specs = layer_specs(cfg)
    if cfg.enc_layers > 0:
        specs = [LayerSpec(mixer="attn", mlp="dense", cross_attn=True,
                           use_rope=cfg.use_rope)] * cfg.n_layers
    if start_pos:
        bad = sorted({sp.mixer for sp in specs if sp.mixer != "attn"})
        if page_tables is None:
            raise ValueError(
                "lm_prefill: start_pos > 0 needs page_tables — the cached "
                "prefix lives in shared pool pages (DESIGN.md §12)")
        if bad or cfg.enc_layers > 0:
            raise ValueError(
                "lm_prefill: start_pos > 0 needs an attention-only stack — "
                f"recurrent/cross-attn mixers ({bad or ['cross-attn']}) carry "
                "state the cached pages do not hold")

    # mirrors _apply_layer (which cannot thread caches) — keep residual
    # sharding and out_seq in sync with it; MoE layers serve through the
    # dropless moe_serve, as decode does (no capacity drops)
    x = logical_constraint(x, "batch", "seq", "embed")
    new_caches: List[Dict] = []
    for lp, spec, cache in zip(params["layers"], specs, caches):
        h_in = _norm_apply(cfg, lp["pre_norm"], x)
        if spec.mixer == "attn":
            h, cache = attention_prefill(
                lp["attn"], h_in, cache,
                num_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                head_dim=cfg.head_dim_(), positions=positions,
                window=spec.window, chunk=cfg.attn_chunk,
                rope_theta=cfg.rope_theta, rope=spec.rope,
                mrope_sections=cfg.mrope_sections,
                use_rope=spec.use_rope, accum=_accum(cfg),
                out_seq=_out_seq(cfg), page_table=page_tables,
                paged_impl=cfg.paged_attn_impl, start_pos=start_pos,
            )
            if spec.cross_attn:
                xc = _norm_apply(cfg, lp["cross_norm"], x + h)
                hc = cross_attention_prefill(
                    lp["cross"], xc, cache,
                    num_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                    head_dim=cfg.head_dim_(), chunk=cfg.attn_chunk,
                )
                h = h + hc
        elif spec.mixer == "mamba":
            h, cache = mamba_prefill(lp["mamba"], h_in, cache, chunk=cfg.ssm_chunk)
        elif spec.mixer == "mlstm":
            h, cache = mlstm_prefill(lp["mlstm"], h_in, cache,
                                     num_heads=cfg.n_heads, chunk=cfg.ssm_chunk)
        elif spec.mixer == "slstm":
            h, cache = slstm_prefill(lp["slstm"], h_in, cache,
                                     num_heads=cfg.n_heads)
        else:
            h = jnp.zeros_like(x)
        x = _residual(cfg, x + h)
        if spec.mlp == "dense":
            # keep in sync with _apply_layer: residual fused into w_down
            x = _residual(cfg, mlp_apply(
                lp["mlp"], _norm_apply(cfg, lp["post_norm"], x),
                activation=cfg.activation, accum=_accum(cfg),
                out_seq=_out_seq(cfg), residual=x))
        elif spec.mlp == "moe":
            y, _ = _moe_serve(cfg, lp["moe"],
                              _norm_apply(cfg, lp["post_norm"], x))
            x = _residual(cfg, x + y)
        new_caches.append(cache)

    x = _norm_apply(cfg, params["final_norm"], x)
    head = params.get("lm_head", params["embed"])
    logits = unembed_logits(head, x)
    if cfg.logits_softcap:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits, new_caches


def _nucleus_filter(logits: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """Top-p (nucleus) mask: keep the smallest prefix of the
    probability-sorted vocab whose mass reaches ``top_p`` (always at
    least the top-1 token); everything else goes to -inf.

    The keep set is decided *positionally* in sorted order and scattered
    back through the inverse permutation — comparing against the
    threshold logit value would keep every token tied at the threshold,
    letting the kept mass blow well past ``top_p`` on tied logits.  Ties
    break by sorted position (stable sort: lowest vocab id first)."""
    order = jnp.argsort(-logits, axis=-1)                   # descending, stable
    srt = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(srt, axis=-1)
    # a token stays if the mass strictly *before* it is < top_p (>=1 kept)
    keep_sorted = (jnp.cumsum(probs, axis=-1) - probs) < top_p
    inv = jnp.argsort(order, axis=-1)                       # undo the sort
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, logits, -jnp.inf)


def _select_token(
    logits: jnp.ndarray,            # (B, V) fp32
    rng: jnp.ndarray,
    *,
    temperature: float,
    top_k: Optional[int],
    top_p: Optional[float],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy argmax (temperature <= 0) or filtered sampling — all on
    device.  Returns ((B,) int32 tokens, advanced rng)."""
    if not temperature or temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), rng
    lg = logits.astype(jnp.float32) / temperature
    if top_k is not None and 0 < top_k < lg.shape[-1]:
        # positional keep set, like _nucleus_filter: comparing against the
        # k-th *value* would keep every logit tied at it (>> k tokens on a
        # tie plateau); ranks break ties by vocab id (stable sort)
        ranks = jnp.argsort(jnp.argsort(-lg, axis=-1), axis=-1)
        lg = jnp.where(ranks < top_k, lg, -jnp.inf)
    if top_p is not None and top_p < 1.0:
        lg = _nucleus_filter(lg, top_p)
    rng, sub = jax.random.split(rng)
    return jax.random.categorical(sub, lg, axis=-1).astype(jnp.int32), rng


def _select_token_rows(
    logits: jnp.ndarray,            # (B, V) fp32
    rngs: jnp.ndarray,              # (B, 2) uint32 per-row keys
    temperature: jnp.ndarray,       # (B,) fp32; <= 0 rows are greedy
    top_k: jnp.ndarray,             # (B,) int32; <= 0 disables the filter
    top_p: jnp.ndarray,             # (B,) fp32; >= 1 disables the filter
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row token selection with *traced* ``(B,)`` sampling params —
    the continuous-batching analogue of :func:`_select_token`, where
    co-batched requests each carry their own temperature/top-k/top-p.

    Row semantics match ``_select_token`` **bitwise** for the same scalar
    params: disabled filters select the *unfiltered* logits (not a
    filtered copy that merely looks equivalent), greedy rows never
    advance their rng, and sampled rows split exactly once per call — so
    a request's stream is independent of what its co-batch is doing.
    Returns ((B,) int32 tokens, advanced per-row rngs)."""
    v = logits.shape[-1]

    def row(lg, rng, t, k, p):
        greedy = jnp.argmax(lg).astype(jnp.int32)
        scaled = lg / jnp.where(t > 0.0, t, 1.0)
        # rank-based top-k keep set (ties break by vocab id, like
        # _select_token); k outside (0, V) keeps every rank
        ranks = jnp.argsort(jnp.argsort(-scaled))
        kk = jnp.where((k > 0) & (k < v), k, v)
        lk = jnp.where(ranks < kk, scaled, -jnp.inf)
        lp = jnp.where(p < 1.0, _nucleus_filter(lk[None], p)[0], lk)
        rng2, sub = jax.random.split(rng)
        sampled = jax.random.categorical(sub, lp).astype(jnp.int32)
        tok = jnp.where(t > 0.0, sampled, greedy)
        return tok, jnp.where(t > 0.0, rng2, rng)

    return jax.vmap(row)(
        logits.astype(jnp.float32), rngs,
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32))


def lm_generate(
    params: Dict,
    caches: List[Dict],
    first_token: jnp.ndarray,       # (B, 1) int32 — usually argmax of prefill
    start_len: jnp.ndarray,         # scalar or (B,) int32: tokens in cache
    num_tokens: int,                # static: tokens to emit
    cfg: ModelConfig,
    *,
    temperature: float = 0.0,       # <= 0: greedy argmax
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    key: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, List[Dict]]:
    """On-device decode loop: ``num_tokens`` steps in ONE ``jax.lax.scan``
    — the caches ride the carry and token selection (greedy argmax, or
    temperature/top-k/top-p sampling with ``key``) happens on device, so
    there is zero host transfer per generated token.

    ``eos_id`` turns on EOS handling *inside* the scan: per-sequence
    ``done`` flags ride the carry, finished rows keep emitting ``eos_id``,
    and once every row is done the decode step body is skipped via
    ``lax.cond`` (the carry passes through untouched) — early exit without
    a single host sync.

    ``start_len`` may be per-row ``(B,)`` for ragged (right-padded)
    prompts: each row continues from its own prompt length — rope
    positions, cache writes and attention masks all stay per-row, so no
    row ever attends over another row's padding slots.

    Emits the running token *before* each decode step (so
    ``tokens[:, 0] == first_token``), matching the per-token serve loop it
    replaces.  Returns (tokens (B, num_tokens) int32, caches)."""
    start_len = jnp.asarray(start_len, jnp.int32)
    b = first_token.shape[0]
    select = functools.partial(
        _select_token, temperature=temperature, top_k=top_k, top_p=top_p)
    rng0 = key if key is not None else jax.random.PRNGKey(0)

    def live_step(i, operand):
        tok, rng, cs = operand
        logits, cs = lm_decode(params, cs, {"tokens": tok}, start_len + i, cfg)
        nxt, rng = select(logits[:, -1], rng)
        return nxt[:, None], rng, cs

    def step(carry, i):
        tok, done, rng, cs = carry
        emit = tok[:, 0]
        if eos_id is not None:
            done = done | (emit == eos_id)
            # mask-and-carry: skip the whole decode step once every row
            # is finished; finished rows keep emitting eos_id
            nxt, rng, cs = jax.lax.cond(
                jnp.all(done), lambda op: op, functools.partial(live_step, i),
                (tok, rng, cs))
            nxt = jnp.where(done[:, None], jnp.asarray(eos_id, jnp.int32), nxt)
        else:
            nxt, rng, cs = live_step(i, (tok, rng, cs))
        return (nxt, done, rng, cs), emit

    carry0 = (first_token.astype(jnp.int32), jnp.zeros((b,), bool),
              rng0, caches)
    (_, _, _, caches), toks = jax.lax.scan(
        step, carry0, jnp.arange(num_tokens, dtype=jnp.int32),
    )
    return jnp.moveaxis(toks, 0, 1), caches
