"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read:
device busy time, per-program and per-kernel device time, the longest
device operations and the longest idle gaps with what the host was doing.

A device plane (``/device:TPU:<n>``) holds an ``XLA Modules`` line -- one
event per program execution, named after the jitted function -- and an
``XLA Ops`` line -- one event per operation, a Pallas kernel among them.
Host threads hold the harness's own ``bench.*`` annotations.  Host and
device events share one clock in the trace.

    python -m bench.trace <file.xplane.pb>   # print what a trace holds
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"
HOST_PREFIX = "bench."
# "%name.12 = f32[...] op(...)": an HLO instruction, named with its suffix
_BASE = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")


@dataclasses.dataclass
class Timeline:
    """One device: executions of programs and of operations, in ns."""
    name: str
    modules: List[Tuple[str, int, int]]      # (name, start, end)
    ops: List[Tuple[str, int, int]]          # (name, start, end)


@dataclasses.dataclass
class Reduced:
    devices: List[Timeline]
    host: List[Tuple[str, int, int]]         # harness spans (name, start, end)
    start_ns: int
    end_ns: int

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([_union(d.ops, self.start_ns, self.end_ns)
                              for d in self.devices])) * 1e-9

    def module_time(self, pattern: str) -> Tuple[float, int]:
        """(device seconds, executions) of programs whose name matches
        ``pattern``, summed over devices."""
        rx = re.compile(pattern)
        s, n = 0, 0
        for d in self.devices:
            for name, a, b in d.modules:
                if rx.search(name):
                    s += b - a
                    n += 1
        return s * 1e-9, n

    def op_time(self, op_pattern: str, module_pattern: str = "") -> float:
        """Device seconds of operations matching ``op_pattern`` that ran
        inside an execution of a program matching ``module_pattern``."""
        orx, mrx = re.compile(op_pattern), re.compile(module_pattern)
        total = 0
        for d in self.devices:
            spans = sorted((a, b) for name, a, b in d.modules
                           if mrx.search(name))
            starts = np.asarray([a for a, _ in spans], np.int64)
            for name, a, b in d.ops:
                if not orx.search(name):
                    continue
                i = int(np.searchsorted(starts, a, side="right")) - 1
                if i >= 0 and a < spans[i][1]:
                    total += b - a
        return total * 1e-9

    def kernel_time(self, kernel: str, module_pattern: str = "") -> float:
        """Device seconds of the Pallas kernel ``kernel`` -- a
        ``tpu_custom_call`` named after the jitted function that wraps
        the ``pallas_call`` -- inside programs matching
        ``module_pattern``."""
        return self.op_time(
            rf'^%{re.escape(kernel)}(\.\d+)? = .*custom_call_target="tpu_custom_call"',
            module_pattern)

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations with the most device time (s), summed
        over the instructions of one name (``%fusion.12`` and
        ``%fusion.40`` are both ``fusion``).  Operations that hold others,
        such as a loop around its body, are left out."""
        acc: Dict[str, int] = {}
        for d in self.devices:
            ops = sorted(d.ops, key=lambda o: (o[1], -o[2]))
            for i, (name, a, b) in enumerate(ops):
                if i + 1 < len(ops) and ops[i + 1][1] < b:
                    continue                      # holds the next operation
                base = _BASE.match(name)
                key = base.group(1) if base else name[:80]
                acc[key] = acc.get(key, 0) + (b - a)
        best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest gaps between operations on the first device,
        each named by the harness span that covers most of it."""
        if not self.devices:
            return []
        iv = _merged(self.devices[0].ops, self.start_ns, self.end_ns)
        edges = [self.start_ns] + [x for ab in iv for x in ab] + [self.end_ns]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            best, cover = "no harness span", 0
            for name, s, e in self.host:
                c = min(b, e) - max(a, s)
                if c > cover:
                    best, cover = name, c
            out.append([best, (b - a) * 1e-9])
        return out


def _merged(ops: Sequence[Tuple[str, int, int]], lo: int, hi: int):
    iv = sorted((max(a, lo), min(b, hi)) for _, a, b in ops
                if b > lo and a < hi)
    out: List[List[int]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _union(ops, lo, hi) -> int:
    return sum(b - a for a, b in _merged(ops, lo, hi))


def find(log_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.end_ns)


def reduce(path: str, span: Optional[Tuple[int, int]] = None) -> Reduced:
    """Read ``path``.  ``span`` bounds the window (ns on the trace's
    clock); by default it runs from the start of the first harness
    annotation to the end of the last, or over every device event where
    the trace holds no annotation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append(Timeline(
                plane.name,
                list(_events(lines[MODULES])) if MODULES in lines else [],
                list(_events(lines[OPS])) if OPS in lines else []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line)
                            if ev[0].startswith(HOST_PREFIX))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    host.sort(key=lambda h: h[1])
    if span is None and host:
        span = (host[0][1], max(b for _, _, b in host))
    elif span is None:
        ts = [t for d in devices for _, a, b in d.ops for t in (a, b)]
        span = (min(ts), max(ts)) if ts else (0, 0)
    return Reduced(devices, host, *span)


def describe(path: str, out=sys.stdout) -> None:
    """Print the planes, lines and most frequent event names of a trace,
    with one event's stats per line: for reading a trace by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            names: Dict[str, int] = {}
            first = None
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
                if first is None:
                    first = e
            if not names:
                continue
            common = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print(f"  line {line.name!r}: {sum(names.values())} events, "
                  f"{len(names)} names; {common}", file=out)
            try:
                stats = dict(first.stats)
            except Exception as e:  # noqa: BLE001 - printing only
                stats = f"<stats unreadable: {e}>"
            print(f"    first: {first.name!r} start {first.start_ns} end "
                  f"{first.end_ns} stats {str(stats)[:600]}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
