"""The program's own spans in a profiler trace, put on the device's clock.

``ServingEngine.step`` (``src/repro/serving/engine.py``) and
``analysis/runtime.sync_region`` record ``repro.*`` spans with
``jax.profiler.TraceAnnotation``, their ids as event stats:
``repro.step`` (``tick``) holds ``repro.verify_index``, ``repro.admit``
(``free``) with one ``repro.prefill`` (``rid``, ``tokens``,
``hit_pages``) and one ``repro.sync.admission`` per admitted request,
``repro.cow_guard``, ``repro.dispatch`` (``ticks``, ``active``),
``repro.sync.decode_chunk`` and ``repro.commit``.

The device's events in a trace are offset from the host's by about a
millisecond, and the offset is not recorded.  The program's spans
bracket it: a decode chunk cannot start on the device before its
``repro.dispatch`` starts, nor end after its ``repro.sync.decode_chunk``
ends (``clock_offset_ns``).  With host spans moved onto the device's
clock, each idle gap of the device is named by what ``step()`` was
doing (``name_gaps``, ``idle_by_span``).

``bench/trace.py``'s ``reduce`` reads the harness's ``bench.*`` spans
and the device; this module reads the program's spans beside it:

    python -m bench.spans <file.xplane.pb>   # print what the spans say
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from bench import trace

PREFIX = "repro."
DISPATCH, SYNC = "repro.dispatch", "repro.sync.decode_chunk"
PREFILL = "repro.prefill"
DECODE_PROGRAM = "_decode_chunk"
# JAX's host tracer records each call of a jitted function under this name
LAUNCH = "PjitFunction({})"
NO_SPAN = "no harness span"      # bench/trace.py's name for an unheld gap

Span = Tuple[str, int, int, Dict[str, object]]   # (name, start, end, stats)


class Offset(NamedTuple):
    """Device clock = host clock + δ, with ``lo <= δ <= hi`` (ns), from
    ``pairs`` decode chunks."""
    lo: int
    hi: int
    pairs: int

    @property
    def mid(self) -> float:
        return (self.lo + self.hi) / 2


def program_spans(path: str) -> List[Span]:
    """The ``repro.*`` host events of the trace at ``path``, by start."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((e.name, int(e.start_ns), int(e.end_ns),
                          dict(e.stats))
                         for e in line.events if e.name.startswith(PREFIX))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return spans


def launches(path: str, module: str = DECODE_PROGRAM) -> List[int]:
    """Starts of the host's calls of the jitted ``module`` in the trace at
    ``path`` (JAX's own ``PjitFunction(<module>)`` events)."""
    from jax.profiler import ProfileData

    name = LAUNCH.format(module)
    return sorted(int(e.start_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines
                  for e in line.events if e.name == name)


def _named(spans: Sequence, name: str) -> List:
    return [s for s in spans if s[0] == name]


def _chunk_windows(program: Sequence[Span], calls: Sequence[int] = ()
                   ) -> List[Tuple[int, int]]:
    """(start, end of its ``repro.sync.decode_chunk``) per decode chunk
    whose dispatch and sync are both in the trace.  The start is that of
    the last call in ``calls`` inside the ``repro.dispatch``, or, where
    there is none, of the dispatch."""
    dispatches = _named(program, DISPATCH)
    syncs = _named(program, SYNC)
    starts = np.asarray([s[1] for s in syncs], np.int64)
    calls = np.asarray(sorted(calls), np.int64)
    out = []
    for i, (_, a, b, _) in enumerate(dispatches):
        nxt = dispatches[i + 1][1] if i + 1 < len(dispatches) else None
        j = int(np.searchsorted(starts, b, side="left"))
        k = int(np.searchsorted(calls, b, side="right")) - 1
        if k >= 0 and calls[k] >= a:
            a = int(calls[k])
        if j < len(syncs) and (nxt is None or syncs[j][1] < nxt):
            out.append((a, syncs[j][2]))
    return out


def clock_offset_ns(red: trace.Reduced, program: Sequence[Span],
                    calls: Sequence[int] = (),
                    module: str = DECODE_PROGRAM) -> Optional[Offset]:
    """The offset of the first device's clock from the host's, bracketed
    by causality over the executions of ``module``: each starts no
    earlier than its ``repro.dispatch`` (δ <= device start - dispatch
    start) and ends no later than its ``repro.sync.decode_chunk``
    (δ >= device end - sync end).  ``calls`` (``launches``) tighten the
    first bound: an execution starts no earlier than the jit call that
    launched it, which comes after the dispatch's host->device copies.
    An execution is paired with the chunk whose host window it overlaps
    most; executions and chunks cut off at either end of the trace pair
    with nothing and are dropped.  ``None`` where no pair is found: no
    program spans, or no device.  ``lo > hi`` would mean the two clocks
    do not differ by a constant."""
    if not red.devices:
        return None
    windows = _chunk_windows(program, calls)
    execs = [(a, b) for name, a, b in red.devices[0].modules
             if module in name]
    best: Dict[int, Tuple[int, int, int]] = {}   # window -> (overlap, a, b)
    for a, b in execs:
        cover = [min(b, we) - max(a, ws) for ws, we in windows]
        if not cover or max(cover) <= 0:
            continue
        w = int(np.argmax(cover))
        if w not in best or cover[w] > best[w][0]:
            best[w] = (cover[w], a, b)
    if not best:
        return None
    lo = max(b - windows[w][1] for w, (_, _, b) in best.items())
    hi = min(a - windows[w][0] for w, (_, a, _) in best.items())
    return Offset(int(lo), int(hi), len(best))


def _shifted(spans: Sequence, delta: float) -> List[Tuple[str, float, float]]:
    return [(s[0], s[1] + delta, s[2] + delta) for s in spans]


def _delta(offset: Optional[Offset]) -> float:
    return 0.0 if offset is None else offset.mid


def _gaps(red: trace.Reduced) -> List[Tuple[int, int]]:
    """The first device's idle gaps in the window, as ``Reduced.idle_gaps``
    finds them."""
    if not red.devices:
        return []
    iv = trace._merged(red.devices[0].ops, red.start_ns, red.end_ns)
    edges = [red.start_ns] + [x for ab in iv for x in ab] + [red.end_ns]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def name_gaps(red: trace.Reduced, program: Sequence[Span],
              offset: Optional[Offset], n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of the first device, as
    ``Reduced.idle_gaps`` lists them, each named by the innermost span of
    the program's and the harness's that covers more than half of it --
    host spans moved onto the device's clock by the midpoint of
    ``offset`` -- or, where none does, by the span that covers most of
    it.  Without program spans the names are ``idle_gaps``'s."""
    spans = _shifted(list(program) + list(red.host), _delta(offset))
    gaps = sorted(_gaps(red), key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        best, cover, inner = NO_SPAN, 0, None
        for name, s, e in spans:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
            if 2 * c > b - a and (inner is None or e - s < inner[1]):
                inner = (name, e - s)
        out.append([inner[0] if inner else best, (b - a) * 1e-9])
    return out


def _innermost(spans: Sequence[Tuple[str, float, float]]):
    """The innermost span at each moment: (edges, names), where
    ``names[i]`` holds over ``[edges[i], edges[i + 1])``, from -inf to
    +inf (``NO_SPAN`` where no span does)."""
    inner = np.unique([t for _, a, b in spans for t in (a, b)])
    edges = np.concatenate([[-np.inf], inner, [np.inf]])
    names = [NO_SPAN] * (len(edges) - 1)
    for i in range(1, len(inner)):
        m = (inner[i - 1] + inner[i]) / 2
        held = [(b - a, name) for name, a, b in spans if a <= m < b]
        if held:
            names[i] = min(held)[1]
    return edges, names


def _time_by_name(edges, names, intervals: Sequence[Tuple[float, float]]
                  ) -> Dict[str, float]:
    """Seconds of the disjoint ``intervals`` under each name of
    ``_innermost``, most first."""
    if not intervals:
        return {}
    iv = np.asarray(sorted(intervals), np.float64)
    starts, lengths = iv[:, 0], iv[:, 1] - iv[:, 0]
    before = np.concatenate([[0.0], np.cumsum(lengths)])
    i = np.searchsorted(starts, edges, side="right")
    j = np.maximum(i - 1, 0)
    # interval time before each edge: whole intervals, then part of one
    covered = np.where(
        i > 0, before[j] + np.clip(edges - starts[j], 0.0, lengths[j]), 0.0)
    out: Dict[str, float] = {}
    for name, t in zip(names, np.diff(covered)):
        if t > 0:
            out[name] = out.get(name, 0.0) + float(t) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_by_span(red: trace.Reduced, program: Sequence[Span],
                 offset: Optional[Offset]) -> Dict[str, float]:
    """Seconds of the first device's idle time in the window under each
    innermost host span, moved onto the device's clock by the midpoint
    of ``offset``."""
    edges, names = _innermost(_shifted(list(program) + list(red.host),
                                       _delta(offset)))
    return _time_by_name(edges, names, _gaps(red))


def boundaries(program: Sequence[Span], lo: float = -np.inf,
               hi: float = np.inf) -> List[Tuple[int, int]]:
    """Per decode chunk whose ``repro.sync.decode_chunk`` ends in
    ``[lo, hi]``: (that end, the end of the next ``repro.dispatch``) --
    the host's work between two chunks while the device waits."""
    ends = np.asarray([s[2] for s in _named(program, DISPATCH)], np.int64)
    out = []
    for _, _, b, _ in _named(program, SYNC):
        j = int(np.searchsorted(ends, b, side="right"))
        if lo <= b <= hi and j < len(ends):
            out.append((b, int(ends[j])))
    return out


def boundary_by_span(red: trace.Reduced, program: Sequence[Span]
                     ) -> Dict[str, float]:
    """Seconds of the host's chunk boundaries (``boundaries``) in the
    window under each innermost span."""
    edges, names = _innermost(_shifted(list(program) + list(red.host), 0))
    return _time_by_name(edges, names,
                         boundaries(program, red.start_ns, red.end_ns))


def prefills(program: Sequence[Span], lo: float = -np.inf,
             hi: float = np.inf) -> List[Span]:
    """The ``repro.prefill`` spans that end in ``[lo, hi]``."""
    return [s for s in _named(program, PREFILL) if lo <= s[2] <= hi]


def _mean_ms(durations: Sequence[float]) -> Optional[float]:
    return float(np.mean(durations)) * 1e-6 if len(durations) else None


def _offset(off: Optional[Offset]) -> Optional[Dict]:
    return None if off is None else {**off._asdict(), "mid": off.mid}


def summary(path: str) -> Dict:
    """What the program's spans say of the trace at ``path``."""
    red = trace.reduce(path)
    program = program_spans(path)
    by_spans = clock_offset_ns(red, program)
    off = clock_offset_ns(red, program, launches(path)) or by_spans
    bnd = boundaries(program, red.start_ns, red.end_ns)
    pre = prefills(program, red.start_ns, red.end_ns)
    steps = len(_named(program, "repro.step"))
    return {
        "window_s": red.window_s,
        "program_spans": len(program),
        "spans_per_step": len(program) / steps if steps else None,
        "clock_offset_ns": _offset(by_spans),
        "clock_offset_launch_ns": _offset(off),
        "boundary_ms": {"mean": _mean_ms([b - a for a, b in bnd]),
                        "n": len(bnd)},
        "admit_ms": {"mean": _mean_ms([b - a for _, a, b, _ in pre]),
                     "n": len(pre)},
        "idle_s": red.window_s - red.busy_s(),
        "idle_gaps": name_gaps(red, program, off, 10),
        "idle_by_span": idle_by_span(red, program, off),
        "boundary_by_span": boundary_by_span(red, program),
    }


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
