"""Finds what a cell is made of by name: ``BENCHMARK.json`` at the root of
the checkout, configurations by their ``file`` entry, traffic mixes under
``traffic/<name>.json``, per-layer metric readers under
``metrics/<name>.py`` and plain references under ``references/<name>.py``.

A later change adds a configuration, a traffic mix or a metric by adding a
file and an entry in ``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from fnmatch import fnmatchcase
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent

# projections the serving program quantizes in a dense decoder block
QUANT_LEAVES = ("wq", "wk", "wv", "wo", "wi", "wd")


class BenchError(RuntimeError):
    """The benchmark's own inputs are missing or malformed."""


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def load_benchmark(repo: Path = REPO) -> dict:
    return _json(repo / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, repo: Path = REPO) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(repo / c["file"])
    raise BenchError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = HERE) -> dict:
    return _json(root / "traffic" / f"{name}.json")


def _module(path: Path, what: str):
    if not path.is_file():
        raise BenchError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(f"perfbench_{what}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = HERE):
    """The ``read(run) -> float | None`` function of per-layer metric ``name``."""
    return _module(root / "metrics" / f"{name}.py", "metric").read


def reference(name: str, root: Path = HERE):
    return _module(root / "references" / f"{name}.py", "reference")


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that cell ``cell`` reports."""
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


def _matches(path: str, pattern: str) -> bool:
    parts = path.split("/")
    return any(fnmatchcase("/".join(parts[i:]), pattern) for i in range(len(parts)))


def resolve_bits(quant: dict, path: str) -> int:
    """Weight bits of the projection at ``path`` under a quant section
    (base ``w_bits``, later rules win, as the serving program resolves it)."""
    bits = quant["w_bits"]
    for pattern, over in quant.get("rules", []):
        if _matches(path, pattern):
            bits = over.get("w_bits", bits)
    return bits


def model_config(cfg: dict):
    """The serving program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    names = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in cfg.items() if k in names})


def quant_spec(quant: dict):
    """The serving program's QuantSpec for a quant section."""
    from repro.core import QLinearConfig, QuantSpec

    base = {k: quant[k] for k in ("w_bits", "a_bits", "detection", "outlier_frac")}
    return QuantSpec(base=QLinearConfig(**base),
                     rules=[(p, dict(o)) for p, o in quant.get("rules", [])],
                     kv_bits=quant["kv_bits"], kv_dtype=quant["kv_dtype"])
