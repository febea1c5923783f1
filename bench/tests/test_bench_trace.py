"""The trace reducer on a trace recorded on a TPU v5e by
``record_trace_fixture.py``: five executions of ``fixture_step`` (a
matmul-tanh fusion of about 11.06 us, then the Pallas kernel, about
7.0 us), each in a ``bench.step`` annotation, with a 50 ms ``bench.idle``
sleep after each.  The numbers below were read off the trace by hand.

In this trace the device's events sit about 1 ms before the host's
annotations around them, so the first execution falls before the window
that the annotations span: the window holds four of the five."""
from pathlib import Path

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
