"""The least work each kernel call needs, from its shapes alone, and the
least time the chip could take for it.

Each function counts what the call must read and write at least once and
the operations its mathematics needs, never what one implementation
happens to move: so the count reads the same whatever implements the
kernel, and no share of a roofline can pass 100% unless the time leaves
out part of the work.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak bandwidth."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def storage_bits(w_bits: int) -> int:
    """Bits one weight index occupies: nibble-packed up to 4, a byte above."""
    return 4 if w_bits <= 4 else 8


def lut_gemm(m: int, k: int, n: int, w_bits: int, a_bits: int,
             x_bytes: int = 2) -> tuple[float, float]:
    """Fused quantize + index GEMM of (m, k) activations against a (k, n)
    weight of ``w_bits`` indices: the packed indices once, the codebooks
    and activation boundaries, activations and per-token scales in, the
    float32 output out; 2*m*k*n operations."""
    books = 4 * (2 ** w_bits + 2 ** a_bits + 2 ** a_bits - 1)
    nbytes = k * n * storage_bits(w_bits) / 8 + books + m * k * x_bytes + m * 4 + m * n * 4
    return 2.0 * m * k * n, float(nbytes)


def orizuru(m: int, n: int, k_out: int, codes: bool = False) -> tuple[float, float]:
    """Dual top-k outlier detection over (m, n) float32 activations: the
    activations in, ``k_out`` values and channels per side out (and the
    (m, n) int32 codes, for the streaming form). Selection needs no
    multiply-adds, so the bound is the bytes alone."""
    nbytes = m * n * 4 + 2 * m * k_out * 8 + (m * n * 4 if codes else 0)
    return 0.0, float(nbytes)


def outliers_per_side(n: int, frac: float) -> int:
    """Outlier channels per side, as the serving program sizes them."""
    return max(1, int(round(n * frac)))


def attended(positions: np.ndarray, window: int) -> np.ndarray:
    """Keys each query attends: its position + 1, capped by the window."""
    ctx = positions + 1
    return np.minimum(ctx, window) if window else ctx


def paged_attention(slot_ids: np.ndarray, positions: np.ndarray, *, heads: int,
                    kv_heads: int, head_dim: int, block_size: int, window: int,
                    kv_bits: int | None, q_bytes: int = 2) -> tuple[float, float]:
    """One layer's paged attention over a packed step: ``slot_ids`` (rows,),
    ``positions`` (rows, S) with -1 for padded cells. Each distinct KV block
    of each slot's real, window-capped context is read once; q in and the
    output out for real cells; 4 * keys * heads * head_dim operations per
    query. The length of the block table does not enter."""
    real = positions >= 0
    ops = 4.0 * attended(positions[real], window).sum() * heads * head_dim
    blocks = 0
    for s in np.unique(slot_ids[real.any(axis=1)]):
        p = positions[(slot_ids == s)[:, None] & real]
        lo = p.min() - attended(p.min(), window) + 1
        blocks += p.max() // block_size - lo // block_size + 1
    per_token = 2 * kv_heads * (head_dim * kv_bits / 8 + 4 if kv_bits else head_dim * 2)
    nbytes = blocks * block_size * per_token + 2 * real.sum() * heads * head_dim * q_bytes
    return float(ops), float(nbytes)


def projections(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, k, n) of each quantized projection of one dense decoder layer."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    ff_in = cfg["d_ff"] * (2 if cfg["act_fn"] in ("silu", "gelu") else 1)
    return [("attn/wq", d, q), ("attn/wk", d, kv), ("attn/wv", d, kv),
            ("attn/wo", q, d), ("mlp/wi", d, ff_in), ("mlp/wd", cfg["d_ff"], d)]


def step_flops(cfg: dict, positions: np.ndarray) -> float:
    """Model operations of the real tokens of one packed step: 2 per weight
    of every projection and of the head, plus attention over each token's
    real, window-capped context."""
    per_layer = sum(k * n for _, k, n in projections(cfg))
    real = positions[positions >= 0]
    keys = attended(real, cfg.get("sliding_window", 0)).sum()
    attn = 4.0 * keys * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    return 2.0 * real.size * (per_layer * cfg["n_layers"]
                              + cfg["d_model"] * cfg["vocab_size"]) + attn
