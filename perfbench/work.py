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
from collections import Counter
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


def layer_windows(cfg: dict) -> list[int]:
    """Each layer's attention window (0: full): ``window_pattern``, one
    period of per-layer windows tiled over the depth, where the
    configuration gives one; else ``sliding_window`` for every layer."""
    pattern = cfg.get("window_pattern") or [cfg.get("sliding_window", 0)]
    return [pattern[i % len(pattern)] for i in range(cfg["n_layers"])]


def _ff_in(cfg: dict, width: int) -> int:
    """Output width of an MLP's input projection (gate and up for gated)."""
    return width * (2 if cfg["act_fn"] in ("silu", "gelu") else 1)


def projections(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, k, n) of each projection that every token of one decoder
    layer passes through: attention, and the dense MLP or, in a
    mixture-of-experts layer (``n_experts``), the shared expert if any."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    out = [("attn/wq", d, q), ("attn/wk", d, kv), ("attn/wv", d, kv), ("attn/wo", q, d)]
    if not cfg.get("n_experts"):
        return out + [("mlp/wi", d, _ff_in(cfg, cfg["d_ff"])), ("mlp/wd", cfg["d_ff"], d)]
    if cfg.get("n_shared_experts"):
        f = cfg["n_shared_experts"] * cfg["shared_expert_d_ff"]
        out += [("moe/shared/wi", d, _ff_in(cfg, f)), ("moe/shared/wd", f, d)]
    return out


def experts(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, k, n) of one routed expert's projections (``d_ff`` is the
    expert width); none in a dense layer."""
    if not cfg.get("n_experts"):
        return []
    d, f = cfg["d_model"], cfg["d_ff"]
    return [("moe/wi", d, _ff_in(cfg, f)), ("moe/wd", f, d)]


def detected_widths(cfg: dict) -> list[int]:
    """Width of each distinct activation tensor that a layer's per-token
    projections quantize: the attention input (shared by wq, wk, wv), wo's,
    and the MLP's (or shared expert's) two inputs."""
    p = projections(cfg)
    return [p[0][1], p[3][1]] + [k for _, k, _ in p[4:]]


def expert_gemm(rows: int, k: int, n: int, n_experts: int, k_active: int, w_bits: int,
                a_bits: int, x_bytes: int = 2) -> tuple[float, float]:
    """Grouped quantize + index GEMM of ``rows`` tokens, each against the
    (k, n) weights of ``k_active`` of ``n_experts`` experts: the packed
    indices and the codebook of the ``min(n_experts, rows * k_active)``
    experts that some token must reach, each read once; the activation
    codebook and boundaries, each token's activations and scale in once, a
    float32 output per (token, expert) pair; 2*rows*k_active*k*n
    operations. A lower bound for any implementation. No reader calls it
    yet: it is the count for the roofline reader of a grouped expert
    kernel, which a configuration with quantized routed experts brings with
    its cell (ROADMAP 2.1)."""
    used = min(n_experts, rows * k_active)
    books = 4 * (used * 2 ** w_bits + 2 ** a_bits + 2 ** a_bits - 1)
    nbytes = (used * k * n * storage_bits(w_bits) / 8 + books + rows * k * x_bytes + rows * 4
              + rows * k_active * n * 4)
    return 2.0 * rows * k_active * k * n, float(nbytes)


def step_flops(cfg: dict, positions: np.ndarray) -> float:
    """Model operations of the real tokens of one packed step: 2 per weight
    of every projection a token passes through (in a mixture-of-experts
    layer its router, its ``experts_per_token`` experts and the shared
    expert with its gate) and of the head, plus attention over each token's
    real context, capped by each layer's window."""
    d = cfg["d_model"]
    per_layer = sum(k * n for _, k, n in projections(cfg))
    if cfg.get("n_experts"):
        per_layer += d * cfg["n_experts"] + cfg["experts_per_token"] * sum(
            k * n for _, k, n in experts(cfg))
        per_layer += d if cfg.get("n_shared_experts") else 0
    real = positions[positions >= 0]
    attn = sum(4.0 * attended(real, w).sum() * cfg["n_heads"] * cfg["head_dim"] * n
               for w, n in Counter(layer_windows(cfg)).items())
    return 2.0 * real.size * (per_layer * cfg["n_layers"] + d * cfg["vocab_size"]) + attn
