"""The system under test: the program's ``ServingEngine`` serving one
configuration, driven through its normal entry (``submit`` / ``step``).

This is the only file of the harness that touches the engine; what it
reads back is what the engine exposes: emitted tokens, the first-token
stamp, request status, and its counters.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import numpy as np

from bench import model


class Server:
    """One engine over the configuration ``cfg`` with weights from
    ``seed``, sized by the traffic mix ``mix``."""

    def __init__(self, cfg: Dict, name: str, mix: Dict, seed: int):
        from repro.serving import ServingEngine

        t0 = time.perf_counter()
        self.cfg, self.mix = cfg, mix
        self.timings: Dict[str, float] = {}
        ad = model.adapter(cfg)
        self.model_cfg = ad.program_config(cfg, name)
        # the very arrays the reference regenerates, laid out for the
        # program (a copy of slices: exact)
        params = jax.jit(ad.program_params)(model.weights(cfg, seed))
        jax.block_until_ready(params)
        self.timings["weights_s"] = time.perf_counter() - t0
        self.tiles: Optional[Dict[str, int]] = None
        if cfg.get("prune"):
            params = self._pack(params, cfg["prune"])
            jax.block_until_ready(params)
            self.timings["pack_s"] = time.perf_counter() - t0 - \
                self.timings["weights_s"]
        serving = cfg["serving"]
        self.engine = ServingEngine(
            params, self.model_cfg, num_slots=mix["slots"],
            page_size=serving["page_size"], max_seq_len=mix["max_seq_len"],
            ticks_per_sync=serving["ticks_per_sync"],
            nan_guard=serving["nan_guard"],
            prefix_caching=serving["prefix_caching"], eos_id=None)

    def _pack(self, params, prune: Dict):
        """BSR-pack the pruned matmuls with the program's own packer.  The
        pruned tiles are already exactly zero, so no mask is passed."""
        from repro.core import BlockingSpec
        from repro.core.masks import build_structures
        from repro.sparse import pack_params
        from repro.sparse.prune import DEFAULT_EXCLUDE, DEFAULT_INCLUDE

        bk, bn = prune["block"]
        structures = build_structures(
            params, BlockingSpec(bk=bk, bn=bn), include=DEFAULT_INCLUDE,
            exclude=DEFAULT_EXCLUDE, min_size=1)
        packed = pack_params(params, None, structures)
        total = structures.total_structures
        nnz = 0
        for info in structures.infos:
            leaf = packed
            for part in info.path.split("/"):
                leaf = leaf[int(part)] if isinstance(leaf, list) else leaf[part]
            nnz += leaf.nnz_blocks
        self.tiles = {"total": int(total), "kept": int(nnz)}
        return packed

    # -- the engine's entry ------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int) -> int:
        return self.engine.submit(prompt, max_new, arrival=self.engine.tick)

    def step(self) -> None:
        self.engine.step()

    def busy(self) -> bool:
        e = self.engine
        return bool(e.scheduler.pending) or any(s is not None for s in e.slots)

    def emitted(self) -> Dict[int, int]:
        """Tokens each request holding a slot has emitted so far."""
        return {s.req.rid: len(s.emitted) for s in self.engine.slots
                if s is not None}

    def request(self, rid: int):
        return self.engine.requests[rid]

    def counters(self) -> Dict[str, int]:
        e = self.engine
        return {"active_slot_ticks": e.active_slot_ticks,
                "decode_ticks": e.decode_ticks,
                "chunks": sum(e.chunks_by_ticks.values()),
                **{f"fault.{k}": v for k, v in e.fault_stats.items()}}

    def chunk_error(self) -> Optional[str]:
        """The last decode-chunk exception the engine recovered from."""
        return self.engine.last_chunk_error

    def release_prefix_cache(self) -> None:
        self.engine.release_prefix_cache()

    def close(self) -> None:
        """Drop the engine, its pools and the weights."""
        self.engine = None
