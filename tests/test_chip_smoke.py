"""``chip_smoke.py`` rehearsed on CPU.

On a TPU the script serves qwen1.5-0.5b at its published widths.  Here
the same phases (dense and packed engines, cold and warm runs, the
fault-free and full-budget checks, the prefill-logits check against the
float32 reference, the solo-decode report) run on a 2-layer, d_model-128
cut of that model with the jnp reference kernels, so a change to the
engine API the smoke drives fails here rather than on the chip.
"""
import importlib.util
import sys
from pathlib import Path

import pytest

from repro.configs import get_config, make_smoke

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_at_tiny_size(chip_smoke, capsys):
    cfg = make_smoke(get_config("qwen1.5-0.5b"), n_layers=2,
                     param_dtype="bfloat16", activ_dtype="bfloat16")
    kernels = chip_smoke.run_smoke(
        cfg, prompt_lens=(12, 20, 33, 12, 20), gen=6, slots=4, max_seq=64,
        page_size=16, ticks=4, block=32)
    assert kernels == {"dense": 0, "packed": 0}     # jnp references off-TPU
    out = capsys.readouterr().out
    for name in ("dense", "packed"):
        assert f"{name} warm: 5 requests, 30 tokens" in out
        assert out.count(f"{name}: prompt of") == chip_smoke.N_CHECK


def test_smoke_refuses_a_host_without_tpu(chip_smoke, capsys, monkeypatch):
    monkeypatch.setattr(sys, "argv", [str(SCRIPT)])
    assert chip_smoke.main() == 1
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "not a TPU" in captured.err
