"""The program's spans read beside the device (``bench/spans.py``): on a
synthetic timeline whose device clock runs a known 1.2 ms behind the
host's, on the chip-recorded fixture (which holds no program span), and
on a tiny cell traced on the CPU.

The synthetic timeline has five whole decode chunks, 20 ms apart, and a
chunk cut off at each end: an execution whose spans fell before the
trace, and a last step whose chunk never reached the device.  Each
chunk starts on the device 0.5 ms after its ``repro.dispatch`` starts
and ends 0.5 ms before its ``repro.sync.decode_chunk`` ends, so the
offset's bracket is [-1.7, -0.7] ms; the jit call that launches each
chunk starts 0.3 ms into the dispatch, which narrows it to [-1.7, -1.0].
The fourth step admits a request whose prefill holds the host 7.6 ms."""
import pytest

from bench import serve, spans, trace, traffic
from bench.system import Server
from bench.tests.test_bench_harness import MIXES, TINY
from bench.tests.test_bench_trace import FIXTURE

MS = 1_000_000
DELTA = -1_200_000                   # device clock = host clock + DELTA
PERIOD = 20 * MS
PREFILL_AT, PREFILL_MS = 3, 6        # the fourth step's extra host time


def _step(k: int, whole: bool = True):
    """Host spans (ms after the step's start) and the device execution
    of step ``k``; a step that is not ``whole`` was cut by the trace's
    end after its dispatch."""
    t = k * PERIOD
    p = PREFILL_MS if k == PREFILL_AT else 0
    host = [("repro.verify_index", 0, 1), ("repro.admit", 1, 3 + p),
            ("repro.dispatch", 3 + p, 4 + p)]
    if p:
        host.append(("repro.prefill", 1.2, 2.8 + p))
    if whole:
        host += [("bench.step", 0, 19.5), ("repro.step", 0, 19),
                 ("repro.sync.decode_chunk", 4 + p, 18),
                 ("repro.commit", 18, 19)]
    spans_ = [(n, t + int(a * MS), t + int(b * MS)) for n, a, b in host]
    run = (t + int((3.5 + p) * MS) + DELTA, t + int(17.5 * MS) + DELTA)
    return spans_, run, t + int((3.3 + p) * MS)


def _timeline():
    host, runs, calls = [], [_step(-1)[1]], []
    for k in range(5):
        s, run, call = _step(k)
        host += s
        runs.append(run)
        calls.append(call)
    s, _, call = _step(5, whole=False)
    host += s
    calls.append(call)
    program = sorted([(n, a, b, {}) for n, a, b in host
                      if n.startswith("repro.")], key=lambda s: s[1])
    harness = sorted([h for h in host if h[0].startswith("bench.")],
                     key=lambda h: h[1])
    modules = [("jit__decode_chunk(1)", a, b) for a, b in runs]
    red = trace.Reduced([trace.Timeline("/device:TPU:0", modules, modules)],
                        harness, harness[0][1], max(h[2] for h in harness))
    return red, program, calls


def test_clock_offset_brackets_a_known_offset():
    red, program, calls = _timeline()
    off = spans.clock_offset_ns(red, program)
    assert off == (-1_700_000, -700_000, 5)
    assert off.lo <= DELTA <= off.hi and off.mid == DELTA
    assert spans.clock_offset_ns(red, program, calls) == \
        (-1_700_000, -1_000_000, 5)


def test_clock_offset_is_none_without_program_spans():
    red, program, calls = _timeline()
    assert spans.clock_offset_ns(red, [], calls) is None
    red.devices = []
    assert spans.clock_offset_ns(red, program, calls) is None


def test_gaps_are_named_by_the_innermost_span_on_the_device_clock():
    red, program, _ = _timeline()
    gaps = spans.name_gaps(red, program, spans.clock_offset_ns(red, program),
                           2)
    # the longest gap: 12 ms, 7.6 of them in the prefill, which lies
    # inside repro.admit, repro.step and bench.step
    assert gaps[0][0] == "repro.prefill"
    assert gaps[0][1] == pytest.approx(0.012)
    # a plain boundary: no span covers more than half of its 6 ms; the
    # next repro.step covers most of it
    assert gaps[1] == ["repro.step", pytest.approx(0.006)]


def test_idle_time_by_innermost_span():
    red, program, _ = _timeline()
    idle = spans.idle_by_span(red, program,
                              spans.clock_offset_ns(red, program))
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s())
    # on the device's clock only the last 0.5 ms of each sync is idle
    # (four boundaries and the window's end), and all of each commit
    assert idle["repro.sync.decode_chunk"] == pytest.approx(5 * 0.5e-3)
    assert idle["repro.commit"] == pytest.approx(5 * 1e-3)
    assert idle["repro.prefill"] == pytest.approx(7.6e-3)


def test_boundaries_and_prefills():
    red, program, _ = _timeline()
    bnd = spans.boundaries(program, red.start_ns, red.end_ns)
    # sync end to the next dispatch's end: 6 ms, 12 before the prefill
    assert [(b - a) / MS for a, b in bnd] == \
        pytest.approx([6, 6, 12, 6, 6])
    pre = spans.prefills(program, red.start_ns, red.end_ns)
    assert [(s[2] - s[1]) / MS for s in pre] == pytest.approx([7.6])
    parts = spans.boundary_by_span(red, program)
    assert sum(parts.values()) == pytest.approx(36e-3)
    assert parts["repro.commit"] == pytest.approx(5e-3)


def test_without_program_spans_gaps_keep_their_harness_names():
    red = trace.reduce(FIXTURE)
    assert spans.program_spans(FIXTURE) == []
    assert spans.clock_offset_ns(red, [], spans.launches(FIXTURE)) is None
    assert spans.name_gaps(red, [], None, 5) == red.idle_gaps(5)


def test_tiny_traced_cell_on_the_cpu(tmp_path):
    """The harness's own window, traced on the CPU: the program's spans
    are there and give a boundary and an admission time; with no device
    plane there is no clock offset."""
    cfg, mix, seed = dict(TINY), MIXES["backlog"], 2**32 + 7
    server = Server(cfg, "dense-decode", mix, seed)
    serve.warm_up(server, mix, seed)
    requests = traffic.generate(mix, cfg["vocab_size"], seed, 1.0)
    serve.serve(server, requests, 1.0, str(tmp_path))
    out = spans.summary(trace.find(str(tmp_path)))
    assert out["clock_offset_ns"] is None
    assert out["clock_offset_launch_ns"] is None
    assert out["boundary_ms"]["n"] >= 2 and out["boundary_ms"]["mean"] > 0
    assert out["admit_ms"]["n"] >= 1 and out["admit_ms"]["mean"] > 0
    assert out["spans_per_step"] >= 7
