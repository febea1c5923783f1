"""One run of one cell: set-up, warm-up, the measured window, the metrics
and the correctness check.

The window is a single-threaded open loop: requests are handed to the
engine when they are due (all at once for a backlog), and between
handovers the loop calls ``step()``, which admits, prefills and runs one
decode chunk.  Every token is stamped with the host clock when the
``step()`` that produced it returns.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from collections import deque
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench import check, model, peaks, traffic
from bench import trace as trace_mod
from bench.clock import CompileClock
from bench.system import Server

BENCH = Path(__file__).resolve().parent
TRACE_SECONDS = 10.0      # the traced span: the last seconds of the window
WARM_TICKS_EXTRA = 1      # warm requests outlive one decode chunk


@dataclasses.dataclass
class Record:
    """What the harness saw of one request, on the host clock."""
    due: float
    submit: float
    prompt: np.ndarray
    max_new: int
    stamps: List[float] = dataclasses.field(default_factory=list)  # per token
    first: Optional[float] = None      # the engine's first-token stamp
    status: str = "queued"
    tokens: Optional[np.ndarray] = None


@dataclasses.dataclass
class Context:
    """Everything a metric reader may read.  Times are host
    ``perf_counter`` seconds; ``span`` is the traced part of the window
    (the whole window in an untraced run)."""
    cell: Dict
    cfg: Dict
    mix: Dict
    setup_s: float
    t_open: float
    t_end: float
    records: Dict[int, Record]
    span: tuple
    counters: Dict[str, Dict[str, int]]     # "start" / "end" of the span
    window_compiles: int
    trace: Optional[trace_mod.Reduced]
    peaks: Dict[str, float]
    tiles: Optional[Dict[str, int]]

    def tokens_in(self, a: float, b: float) -> int:
        return sum(int(np.count_nonzero((s > a) & (s <= b)))
                   for s in (np.asarray(r.stamps) for r in self.records.values()))


def find_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, or the
    file of its base name (``<base>.<cells>`` shares ``<base>.py``)."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{BENCH / 'metrics'}")


def metrics_for(bench: Dict, cell: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_limits(cell: str) -> Dict:
    path = BENCH / "limits" / f"{cell}.json"
    if not path.exists():
        return {}
    with open(path) as f:
        return json.load(f)


def warm_up(server: Server, mix: Dict, seed: int) -> None:
    """Compile and run every shape the window uses: one prefill per prompt
    length of the mix and the decode chunk, through the engine itself."""
    rng = np.random.default_rng([int(seed), 3])
    ticks = server.cfg["serving"]["ticks_per_sync"]
    vocab = server.cfg["vocab_size"]
    lengths = traffic.lengths(mix)
    for i in range(0, len(lengths), mix["slots"]):
        for n in lengths[i:i + mix["slots"]]:
            server.submit(rng.integers(0, vocab, size=n, dtype=np.int32),
                          ticks + WARM_TICKS_EXTRA)
        while server.busy():
            server.step()
    server.release_prefix_cache()


def serve(server: Server, requests: List[traffic.Request], seconds: float,
          trace_dir: Optional[str] = None) -> tuple:
    """Drive the window.  Returns (records, t_open, t_end, span,
    counters at the span's start and end)."""
    pending = deque(requests)
    records: Dict[int, Record] = {}
    live: set = set()
    t_open = time.perf_counter()
    close = t_open + seconds
    trace_at = close - min(TRACE_SECONDS, seconds) if trace_dir else None
    span_start, counters0 = t_open, server.counters()
    while True:
        now = time.perf_counter()
        if now >= close:
            break
        if trace_at is not None and now >= trace_at:
            jax.profiler.start_trace(trace_dir, profiler_options=_options())
            trace_at = None
            span_start, counters0 = time.perf_counter(), server.counters()
        while pending and t_open + pending[0].due <= now:
            r = pending.popleft()
            with jax.profiler.TraceAnnotation("bench.submit"):
                rid = server.submit(r.prompt, r.max_new)
            records[rid] = Record(t_open + r.due, time.perf_counter(),
                                  r.prompt, r.max_new)
            live.add(rid)
        if not server.busy():
            nxt = t_open + pending[0].due if pending else close
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(nxt, close) - time.perf_counter()))
            continue
        with jax.profiler.TraceAnnotation("bench.step"):
            server.step()
        t = time.perf_counter()
        _stamp(server, records, live, t)
    t_end = time.perf_counter()
    counters1 = server.counters()
    if trace_dir is not None and trace_at is None:
        jax.profiler.stop_trace()
    for rid in live:                     # in flight at the close
        req = server.request(rid)
        records[rid].status = req.status.value
        records[rid].first = req.first_token_time
    return records, t_open, t_end, (span_start, t_end), \
        {"start": counters0, "end": counters1}


def _stamp(server: Server, records, live: set, t: float) -> None:
    now = server.emitted()
    for rid in list(live):
        rec = records[rid]
        if rid in now:
            n = now[rid]
        else:
            req = server.request(rid)
            if not req.terminal:
                continue                 # still waiting for a slot
            n = 0 if req.tokens is None else len(req.tokens)
            rec.status, rec.tokens = req.status.value, req.tokens
            rec.first = req.first_token_time
            live.discard(rid)
        rec.stamps.extend([t] * (n - len(rec.stamps)))


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run_cell(bench: Dict, cell_name: str, seed: int, seconds: float,
             traced: bool, *, t_start: Optional[float] = None,
             log: Callable[[str], None] = None) -> Dict:
    """One run of ``cell_name``; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    cfg = model.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    device = jax.devices()[0]
    table = peaks.peaks(device.device_kind) if device.platform == "tpu" else {}
    clock = CompileClock()

    server = Server(cfg, cell["config"], mix, seed)
    requests = traffic.generate(mix, cfg["vocab_size"], seed, seconds)
    warm_up(server, mix, seed)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: {server.timings} (s); compile "
        f"{clock.seconds:.1f} s {clock.by_event}, {clock.compiles} compiles, "
        f"persistent cache {clock.cache_hits} hits / {clock.cache_misses} "
        f"misses")

    compiles0 = clock.compiles
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        records, t_open, t_end, span, counters = serve(
            server, requests, seconds, trace_dir)
        window_compiles = clock.compiles - compiles0
        stats = device.memory_stats() or {}
        reduced = None
        if traced:
            path = trace_mod.find(trace_dir)
            if path is None:
                raise RuntimeError("the profiler wrote no trace")
            reduced = trace_mod.reduce(path)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window {t_end - t_open:.3f} s: {len(records)} requests, "
        f"{sum(len(r.stamps) for r in records.values())} tokens, "
        f"{window_compiles} compiles")
    ctx = Context(cell=cell, cfg=cfg, mix=mix, setup_s=setup_s, t_open=t_open,
                  t_end=t_end, records=records, span=span, counters=counters,
                  window_compiles=window_compiles, trace=reduced, peaks=table,
                  tiles=server.tiles)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, cell_name, kind):
        value = find_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    faults = counters["end"]
    if server.chunk_error() is not None:
        log("decode chunk failed and recovered: "
            f"{server.chunk_error()[:1500]}")
    server.close()
    server = None
    gc.collect()
    checks = correctness(cfg, mix, seed, records, faults,
                         load_limits(cell_name), log)
    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": len(records),
        "failed": sum(r.status in ("failed", "rejected", "expired",
                                   "cancelled") for r in records.values()),
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": stats.get("peak_bytes_in_use")},
    }
    if traced:
        result["device"]["busy_s"] = reduced.busy_s()
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(10)}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']}) "
            f"{'ok' if c['ok'] else 'FAILED'}")
    return result


def correctness(cfg: Dict, mix: Dict, seed: int, records: Dict[int, Record],
                counters: Dict[str, int], limits: Dict,
                log: Callable[[str], None], control: bool = False
                ) -> Dict[str, Dict]:
    """The numbers compared, each with its limit and verdict.  With
    ``control`` the logit gap is the control's: the tokens the reference
    puts first at the next lower precision, in the program's place
    (``check.gaps``)."""
    lost = [rid for rid, r in records.items()
            if r.status in ("failed", "rejected", "expired", "cancelled")
            or (r.status == "finished"
                and (r.tokens is None or len(r.tokens) != r.max_new))]
    recoveries = (counters["fault.chunk_failures"]
                  + counters["fault.guard_trips"] + counters["fault.degraded"])
    finished = {rid: len(r.tokens) for rid, r in records.items()
                if r.status == "finished" and r.tokens is not None}
    picked = check.sample(finished, int(mix["check_requests"]), seed)
    checks = {
        "lost_requests": {"value": len(lost), "limit": 0,
                          "ok": not lost},
        "engine_recoveries": {"value": int(recoveries), "limit": 0,
                              "ok": recoveries == 0},
        "checked_tokens": {"value": sum(finished[r] for r in picked),
                           "limit": 1, "ok": bool(picked)},
    }
    limit = limits.get("max_logit_gap", {}).get("limit")
    gap = None
    if picked:
        t0 = time.perf_counter()
        w = check.reference_weights(cfg, seed)
        served = [(records[r].prompt, records[r].tokens) for r in picked]
        per = check.gaps(cfg, w, served, mix["max_seq_len"], control=control)
        gap = max(per)
        del w
        log(f"reference over {len(picked)} requests "
            f"({checks['checked_tokens']['value']} tokens) in "
            f"{time.perf_counter() - t0:.3f} s; widest gap per request "
            f"{[round(g, 5) for g in per]}")
    checks["max_logit_gap"] = {
        "value": gap, "limit": limit,
        "ok": gap is not None and limit is not None and gap <= limit}
    return checks
