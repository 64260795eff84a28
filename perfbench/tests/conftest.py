"""Fixtures of the benchmark's tests: a checkout in a temporary directory
holding ``BENCHMARK.json`` and the benchmark's files, with a configuration
small enough to serve on the CPU."""

import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
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

# the program's mixture-of-experts family at smoke size (qwen2-moe's smoke
# widths: 8 experts of 64, top-2, two shared experts), served in bf16 under
# the chat configuration's quantization
MOE = {"family": "moe", "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
       "head_dim": 16, "d_ff": 64, "vocab_size": 32000, "n_experts": 8,
       "experts_per_token": 2, "n_shared_experts": 2, "shared_expert_d_ff": 64,
       "capacity_factor": 2.0, "sliding_window": 0, "reference": "moe_decoder"}

def stacked_weights(shapes, cfg: dict, seed: int) -> dict:
    """The whole parameter tree of ``shapes`` from ``seed``, the blocks
    stacked: the tree that ``weights.rest_maker`` and ``weights.block_maker``
    give piece by piece, which a run never holds whole."""
    from perfbench import weights

    base = weights.seed_key(seed)
    make = weights.block_maker(weights.block_shapes(shapes), cfg)
    n = jax.tree_util.tree_leaves(shapes["blocks"])[0].shape[0]
    blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *[make(base, i) for i in range(n)])
    return dict(weights.rest_maker(shapes, cfg)(base), blocks=blocks)


def whole_logits(ref, params, cfg: dict, tokens):
    """(T, vocab_size) logits of one sequence under reference module ``ref``
    over a whole stacked tree: its ``embed``, ``block`` per layer, ``head``."""
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    n = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
    x, _ = jax.lax.scan(lambda c, pi: (ref.block(cfg, c, pi[0], pos, pi[1]), None),
                        ref.embed(params, cfg, tokens),
                        (params["blocks"], jnp.arange(n, dtype=jnp.int32)))
    return ref.head(params, cfg, x)


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
         "source": "made up for tests on the CPU"}


def make_checkout(root: Path, cfg_name: str = "h2o_danube_1_8b",
                  traffic: str = "chat", limits=None, size=None, **mix_over) -> str:
    """A checkout under ``root`` with one tiny cell; returns the cell's name.
    ``size`` replaces keys of configuration ``cfg_name`` (default ``TINY``)."""
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
