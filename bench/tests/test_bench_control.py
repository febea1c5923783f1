"""The control at a tiny size on the CPU: the reference computed with
float8 matmul inputs, put in the program's place, comes out not correct
through the harness's own check under the limit that sound bfloat16 runs
pass (``bench/control.py`` does the same on the chip at the cells' own
sizes, under their committed limits)."""
import json
from pathlib import Path

import pytest

from bench import control, model, serve, traffic

from .test_bench_harness import MIXES, TINY, TINY_LIMIT

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("seed", [1, 3, 2**35 + 9])
def test_control_fails_where_the_program_passes(monkeypatch, seed):
    monkeypatch.setattr(model, "load_config", lambda name: TINY)
    monkeypatch.setattr(traffic, "load", lambda name: MIXES["backlog"])
    monkeypatch.setattr(serve, "load_limits",
                        lambda cell: {"max_logit_gap": {"limit": TINY_LIMIT}})
    got = control.readings(BENCH, "dense-decode", seed, 2.0)
    program, ctl = got["program"], got["control"]
    assert program["checks"]["checked_tokens"]["value"] > 0
    assert program["correct"], program["checks"]
    assert not ctl["correct"]
    assert ctl["checks"]["max_logit_gap"]["value"] > TINY_LIMIT
    assert ctl["checks"]["max_logit_gap"]["limit"] == TINY_LIMIT
