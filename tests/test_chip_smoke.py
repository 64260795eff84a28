"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
serving-and-check path runs end to end at smoke size with every kernel
route forced on (Pallas interpreted on the CPU)."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

import repro.core.kernel_routing as kr
import repro.models.layers as layers
from repro.configs.base import get_smoke_config


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve annotations there
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_chip_smoke_path_at_smoke_size(chip_smoke, tmp_path, monkeypatch):
    monkeypatch.setattr(kr, "_AUTO_DEFAULT", True)
    monkeypatch.setattr(kr, "_DETECT_AUTO_DEFAULT", True)
    monkeypatch.setattr(layers, "_USE_PAGED_KERNEL", True)
    lines = []
    size = chip_smoke.SmokeSize(n_requests=3, prompt_lens=(5, 12), new_tokens=3,
                                cache_len=32, slots=2)
    rec = chip_smoke.smoke(get_smoke_config("h2o_danube_1_8b"), size, seed=0,
                           workdir=tmp_path / "artifact", log=lines.append)
    assert rec["quantize_s"] > 0 and rec["generate_s"] > 0
    counters = next(s for s in lines if s.startswith("route counters"))
    counts = json.loads(counters.split(": ", 1)[1])
    assert counts["_kernel_calls"] > 0 and counts["_detect_kernel_calls"] > 0
    assert any("greedy agreement" in s for s in lines)
