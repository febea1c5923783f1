"""The trace reducer on a trace recorded on a TPU v5e by
``record_trace_fixture.py``: five executions of ``fixture_step`` (a
matmul-tanh fusion of about 11.06 us, then the Pallas kernel, about
7.0 us), each in a ``bench.step`` annotation, with a 50 ms ``bench.idle``
sleep after each.  The numbers below were read off the trace by hand.

In this trace the device's events sit about 1 ms before the host's
annotations around them, so the first execution falls before the window
that the annotations span: the window holds four of the five."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace

FIXTURE = str(Path(__file__).parent / "fixtures" / "fixture.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace.reduce(FIXTURE)


def test_planes_and_window(red):
    assert [d.name for d in red.devices] == ["/device:TPU:0"]
    assert [h[0] for h in red.host] == ["bench.step", "bench.idle"] * 5
    assert red.window_s == pytest.approx(0.25745, abs=1e-4)


def test_program_and_kernel_time(red):
    seconds, runs = red.module_time("fixture_step")
    assert runs == 5
    assert seconds == pytest.approx(90.618e-6, rel=1e-3)
    assert red.kernel_time("fixture_step", "fixture_step") == \
        pytest.approx(35.167e-6, rel=1e-3)
    assert red.kernel_time("fixture_step", "no_such_program") == 0.0
    assert red.op_time(r"^%convolution_tanh_fusion", "fixture_step") == \
        pytest.approx(55.314e-6, rel=1e-3)


def test_busy_and_idle(red):
    assert red.busy_s() == pytest.approx(72.334e-6, rel=1e-3)
    gaps = red.idle_gaps(5)
    assert [g[0] for g in gaps] == ["bench.idle"] * 5
    assert all(0.0495 < g[1] < 0.0535 for g in gaps)


def test_top_ops_are_leaves_grouped_by_name(red):
    top = red.top_ops(2)
    assert [t[0] for t in top] == ["convolution_tanh_fusion", "fixture_step"]
    assert top[0][1] == pytest.approx(55.314e-6, rel=1e-3)
    assert top[1][1] == pytest.approx(35.167e-6, rel=1e-3)


def _load_copy_share():
    from bench import serve
    return serve.find_reader("copy_share.decode")


def test_copy_share_merges_overlapping_copies_inside_the_program():
    """Two chunk executions of 100 ns each.  In the first an asynchronous
    copy's start (10-40) and done (30-60) overlap, with a plain copy
    (50-70) overlapping the done: 10-70 is 60 ns, not the 80 summed.
    In the second a copy runs 190-230 and is cut at the execution's end
    (200); a fusion does not count.  A copy outside any execution (400)
    does not count either."""
    ops = [("%copy-start.3 = (f32[8], u32[]) copy-start(%p)", 10, 40),
           ("%copy-done.3 = f32[8] copy-done(%copy-start.3)", 30, 60),
           ("%copy = f32[8]{0} copy(%p)", 50, 70),
           ("%fusion.2 = f32[8] fusion(%p)", 120, 180),
           ("%copy.7 = f32[8]{0} copy(%q)", 190, 230),
           ("%copy.9 = f32[8]{0} copy(%q)", 400, 450),
           ("%copy_fusion = f32[8] fusion(%q)", 20, 90)]
    modules = [("jit__decode_chunk(1)", 0, 100), ("jit__decode_chunk(1)", 100, 200),
               ("jit__paged_prefill_step(2)", 380, 500)]
    red = trace.Reduced([trace.Timeline("/device:TPU:0", modules, ops)], [],
                        0, 500)
    assert red.op_union(r"^%copy(-start|-done)?(\.\d+)? = ", "_decode_chunk") \
        == pytest.approx(70e-9)
    ctx = SimpleNamespace(trace=red)
    assert _load_copy_share()(ctx) == pytest.approx(100.0 * 70 / 200)


def test_copy_share_on_the_recorded_trace(red):
    """The fixture holds no decode chunk, so the reader finds nothing to
    read.  Inside ``fixture_step`` it holds one asynchronous copy pair per
    execution (``copy-start`` 13-14 ns, ``copy-done`` 2-3 ns, apart):
    79 ns in all, the union equal to the sum."""
    share = _load_copy_share()
    assert share(SimpleNamespace(trace=red)) is None
    assert share(SimpleNamespace(trace=None)) is None
    copies = r"^%copy(-start|-done)?(\.\d+)? = "
    assert red.op_union(copies, "fixture_step") == pytest.approx(79e-9)
    assert red.op_time(copies, "fixture_step") == pytest.approx(79e-9)
