"""Fixtures of the benchmark's tests: a checkout in a temporary directory
holding ``BENCHMARK.json`` and the benchmark's files, with a configuration
small enough to serve on the CPU."""

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# same kinds of layers as the served configurations, at a width the CPU
# holds; the served vocabulary, since a few hundred ids leave the check's
# control no worse than the program at this width
TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 32000}

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
         "source": "made up for tests on the CPU"}


def make_checkout(root: Path, cfg_name: str = "h2o_danube_1_8b",
                  traffic: str = "chat", limits=None, size=None, **mix_over) -> str:
    """A checkout under ``root`` with one tiny cell; returns the cell's name."""
    shutil.copytree(REPO / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__"))
    cfg = json.loads((REPO / "perfbench" / "configs" / f"{cfg_name}.json").read_text())
    cfg.update(size or TINY)
    if cfg["sliding_window"]:
        cfg["sliding_window"] = 48
    cfg["limits"] = limits or {"wide_gap_share": 1.0}
    (root / "perfbench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((REPO / "perfbench" / "traffic" / f"{traffic}.json").read_text())
    mix.update({"prompt": dict(mix["prompt"], min=16, max=40, median=24),
                "output": dict(mix["output"], min=8, max=24, median=12),
                "serve": {"slots": 4, "cache_len": 64, "token_budget": 8, "block_size": 8},
                "drain_s": 30, "trace_s": 2, "check_requests": 12})
    if mix["loop"] == "open":
        mix.update(rate_rps=4.0, warmup_s=1)
    else:
        mix.update(concurrency=6)
    mix.update(mix_over)
    (root / "perfbench" / "traffic" / "tiny_mix.json").write_text(json.dumps(mix))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    same_mix = {w["name"] for w in bench["workloads"] if w["traffic"] == traffic}
    cell = "tiny.tiny_mix"
    bench["configs"] = [{"name": "tiny", "source": "test", "file": "perfbench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": cell, "config": "tiny", "traffic": "tiny_mix",
                           "chips": 1, "why": "test"}]
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            if "workloads" in m:  # what the cells of this mix report
                m["workloads"] = [cell] if same_mix & set(m["workloads"]) else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


@pytest.fixture
def checkout(tmp_path):
    return lambda **kw: (tmp_path, make_checkout(tmp_path, **kw))
