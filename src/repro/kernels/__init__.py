"""Pallas TPU kernels for the paper's compute hot spots.

* block_sparse_matmul — the §III-C zero-skipping codegen analogue (BSR)
* structure_norms     — Algorithm 2's per-structure value sweep
* paged_attention     — fused page-table walk with online softmax
  (decode + prefill), O(cache_len) not O(max_len), and the in-place
  decode write of the new token's K/V

Each kernel ships with a jit wrapper (ops.py) and a pure-jnp oracle
(ref.py / a non-gathering ref in paged_attention.py); tests sweep
shapes/dtypes with assert_allclose in interpret mode.
"""
from .epilogue import Epilogue, apply_epilogue, make_epilogue
from .ops import (
    bsr_matmul,
    bsr_planes_matmul,
    paged_attention_decode,
    paged_attention_prefill,
    paged_kv_write,
    structure_norms,
)

__all__ = [
    "Epilogue", "apply_epilogue", "make_epilogue",
    "bsr_matmul", "bsr_planes_matmul", "structure_norms",
    "paged_attention_decode", "paged_attention_prefill", "paged_kv_write",
]
