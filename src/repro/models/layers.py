"""Shared building blocks for the model zoo.

Conventions
-----------
* Params are nested dicts of arrays (no framework). A stacked layer axis
  (leading L) is used with ``lax.scan`` so HLO size is O(1) in depth.
* Every projection goes through :func:`dense_apply`, which dispatches between
  a plain fp weight dict and :class:`~repro.core.qlinear.QLinearParams` —
  quantized inference is a drop-in parameter transformation, not a separate
  model definition.
* Tensors are annotated with *logical* axis names via
  ``repro.distributed.sharding.constrain``; the active rule set decides the
  mesh mapping (DP/TP/SP) — model code is mesh-agnostic.
* Attention is memory-efficient when ``chunk > 0``: nested scans over query /
  key chunks with an online-softmax accumulator (flash-style), which is what
  makes the 32k prefill shapes compile within HBM.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro.core import calibration, numerics
from repro.core.qlinear import QLinearParams, qlinear_apply
from repro.distributed.sharding import constrain

__all__ = [
    "dense_init",
    "dense_apply",
    "norm_init",
    "norm_apply",
    "embed_init",
    "rope_apply",
    "sinusoidal_positions",
    "attention_init",
    "attention_apply",
    "init_kv_cache",
    "init_paged_kv_cache",
    "state_quantize",
    "state_dequantize",
    "mlp_init",
    "mlp_apply",
]

_NEG_INF = float(jnp.finfo(jnp.float32).min)


# ---------------------------------------------------------------------------
# dense / norm / embed primitives
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False, scale: float | None = None):
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * s).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p, x: jax.Array, tap_name: str | None = None) -> jax.Array:
    """fp or quantized projection; taps activations during calibration.

    QLinearParams carry their own resolved apply config (``p.cfg``, set by
    the QuantSpec at quantize time) — no ambient configuration is consulted.
    """
    if tap_name is not None and not isinstance(x, jax.core.Tracer):
        x = calibration.tap(tap_name, x)
    if isinstance(p, QLinearParams):
        # names the next quant-health probe site (works on tracers, unlike
        # calibration.tap); no-op unless a numerics collector is active
        numerics.announce(tap_name)
        return qlinear_apply(p, x)
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def norm_init(d: int, kind: str, dtype):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layer":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    elif kind == "layer":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(kind)
    y = y * p["scale"].astype(jnp.float32)
    if kind == "layer":
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def sinusoidal_positions(positions: jax.Array, d: int) -> jax.Array:
    """Classic transformer sinusoidal embedding, (..., d)."""
    half = d // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_apply(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, S, ..., hd); positions: (S,) or (B, S).

    2-D positions carry a per-request absolute position — the continuous-
    batching decode path, where every batch row sits at a different point in
    its own sequence.
    """
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., half)
    # broadcast ((B,) S, 1..., half) against x's (B, S, ..., half)
    ang = ang.reshape(*positions.shape, *([1] * (x.ndim - 3)), half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention_init(key, cfg, dtype, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kv * hd, dtype),
        "wv": dense_init(ks[2], d, kv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype, scale=1.0 / math.sqrt(h * hd)),
    }
    if cross:
        p["gate"] = jnp.zeros((), dtype)  # llama3.2-vision tanh gate
    return p


def _mask(q_pos, k_pos, window: int, causal: bool):
    """(Sq, Sk) bool validity mask; k_pos == -1 marks empty cache slots."""
    valid = (k_pos >= 0)[None, :]
    if causal:
        valid &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            valid &= k_pos[None, :] > q_pos[:, None] - window
    return valid


def _mask_scores(s, msk, k_pos, k_min):
    """Apply an (Sq, Sk) mask to scores s (B, KV, G, Sq, Sk).

    ``k_min`` (B,) optionally also masks keys at positions < k_min[b] per
    batch row — the left-pad exclusion for the fixed-slot fallback engine,
    where a short prompt's pad tokens occupy cache positions [0, pad_len).
    """
    if k_min is not None:
        mb = msk[None] & (k_pos[None, None, :] >= k_min[:, None, None])  # (B,Sq,Sk)
        return jnp.where(mb[:, None, None], s, _NEG_INF)
    return jnp.where(msk[None, None, None], s, _NEG_INF)


def _sdpa_dense(q, k, v, q_pos, k_pos, window, causal, softcap, k_min=None):
    """q: (B,Sq,KV,G,hd); k,v: (B,Sk,KV,hd) -> (B,Sq,KV,G,hd)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bskgh,btkh->bkgst", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = _mask_scores(s, _mask(q_pos, k_pos, window, causal), k_pos, k_min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _sdpa_flash(q, k, v, q_pos, k_pos, window, causal, softcap, q_chunk, k_chunk,
                k_min=None):
    """Flash-style online-softmax attention: nested scan over q/k chunks.

    Peak scores buffer is (B, KV, G, q_chunk, k_chunk) instead of (.., Sq, Sk)
    — this is the difference between 32k-prefill fitting in HBM or not.
    """
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    # pad to chunk multiples (padded q rows discarded; padded k masked via pos=-1)
    pq, pk = (-sq) % q_chunk, (-sk) % k_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=-1)
    nq, nk = (sq + pq) // q_chunk, (sk + pk) // k_chunk
    scale = hd**-0.5

    k_ch = k.reshape(b, nk, k_chunk, kvh, hd).swapaxes(0, 1)
    v_ch = v.reshape(b, nk, k_chunk, kvh, hd).swapaxes(0, 1)
    kp_ch = k_pos.reshape(nk, k_chunk)

    def one_q_chunk(args):
        qc, qp = args  # (B, Cq, KV, G, hd), (Cq,)
        qf = qc.astype(jnp.float32)

        def kv_step(carry, xs):
            m, l, acc = carry
            kc, vc, kp = xs
            s = jnp.einsum("bskgh,btkh->bkgst", qf, kc.astype(jnp.float32)) * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            s = _mask_scores(s, _mask(qp, kp, window, causal), kp, k_min)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkh->bkgsh", p, vc.astype(jnp.float32)
            )
            return (m_new, l, acc), None

        m0 = jnp.full((b, kvh, g, q_chunk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (k_ch, v_ch, kp_ch))
        o = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,Cq,hd)
        return o.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,Cq,KV,G,hd)

    q_ch = q.reshape(b, nq, q_chunk, kvh, g, hd).swapaxes(0, 1)
    qp_ch = q_pos.reshape(nq, q_chunk)
    o = jax.lax.map(one_q_chunk, (q_ch, qp_ch))  # (nq, B, Cq, KV, G, hd)
    o = o.swapaxes(0, 1).reshape(b, sq + pq, kvh, g, hd)
    return o[:, :sq]


def init_kv_cache(cfg, batch: int, cache_len: int, dtype, quantized: bool = False) -> dict:
    """Ring-buffer KV cache for one attention layer.

    slot_pos[j] holds the absolute position stored in slot j (-1 = empty).
    For windowed attention cache_len == window; decode is then O(window)
    compute and memory — this is what makes long_500k decodable for the
    SWA/hybrid archs.

    quantized=True stores K/V as K-Means int4 (two indices per uint8) with a
    per-(token, head) scale — the paper's activation quantization applied to
    the KV cache (beyond-paper, KVQuant-style): 4x less HBM traffic on the
    decode-dominating cache reads.
    """
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    base = {"slot_pos": jnp.full((cache_len,), -1, jnp.int32)}
    if not quantized:
        return base | {
            "k": jnp.zeros((batch, cache_len, kv, hd), dtype),
            "v": jnp.zeros((batch, cache_len, kv, hd), dtype),
        }
    from repro.models.model import _default_codebook  # structural codebook

    return base | {
        "k_idx": jnp.zeros((batch, cache_len, kv, hd // 2), jnp.uint8),
        "v_idx": jnp.zeros((batch, cache_len, kv, hd // 2), jnp.uint8),
        "k_scale": jnp.zeros((batch, cache_len, kv, 1), jnp.float32),
        "v_scale": jnp.zeros((batch, cache_len, kv, 1), jnp.float32),
        "kv_codebook": _default_codebook(4),
    }


def _kv_quantize(x: jax.Array, codebook: jax.Array):
    """x: (B, T, KV, hd) -> (packed idx, per-(token, head) scale)."""
    from repro.core.codebook import assign_via_boundaries
    from repro.core.quantize import pack_int4

    s = jnp.maximum(
        jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)), 1e-12
    )
    idx = assign_via_boundaries((x / s).astype(jnp.float32), codebook)
    return pack_int4(idx), s


def _kv_dequantize(packed: jax.Array, scale: jax.Array, codebook: jax.Array, dtype):
    from repro.core.quantize import lookup, unpack_int4

    return (lookup(codebook, unpack_int4(packed)) * scale).astype(dtype)


def state_quantize(x: jax.Array, codebook: jax.Array):
    """Recurrent-state int4 quantization (Mamba ``h`` (B, di, N) / RG-LRU
    ``h`` (B, di)): per-vector RMS scale over the LAST dim + K-Means boundary
    assignment, the exact KV-pool format reused for SSM state under the
    ``recurrent`` cache policy. Returns (packed idx uint8, scale f32); the
    last dim must be even (two int4 indices per byte)."""
    return _kv_quantize(x, codebook)


def state_dequantize(packed: jax.Array, scale: jax.Array, codebook: jax.Array):
    """Inverse of :func:`state_quantize`; the recurrence runs in f32."""
    return _kv_dequantize(packed, scale, codebook, jnp.float32)


def _cache_write(cache: dict, k, v, positions):
    """Write the last min(S, C) tokens into ring slots; returns new cache.

    Writes use dynamic_update_slice / roll instead of scatter: XLA reliably
    performs DUS in-place on donated buffers, whereas a dynamic-index scatter
    was observed to materialize a full cache copy (+13 GB/device on the
    musicgen decode_32k cell). Contract: ``positions`` are contiguous
    ascending, and multi-token writes start ring-aligned (true for prefill
    from position 0 with C | S or S <= C — the launcher's cases).
    """
    c = cache["slot_pos"].shape[0]
    n_w = min(k.shape[1], c)
    k_w, v_w = k[:, -n_w:], v[:, -n_w:]
    pos_w = positions[-n_w:]
    start = jnp.mod(pos_w[0], c)

    if n_w == c:
        # full overwrite: position p+i lands in slot (p+i) % c == roll by start
        write = lambda _, val: jnp.roll(val, start, axis=1)
        sp = jnp.roll(pos_w, start)
    else:
        write = lambda buf, val: jax.lax.dynamic_update_slice(
            buf, val, (0, start) + (0,) * (buf.ndim - 2)
        )
        sp = jax.lax.dynamic_update_slice(cache["slot_pos"], pos_w, (start,))

    if "k_idx" in cache:
        ki, ks = _kv_quantize(k_w, cache["kv_codebook"])
        vi, vs = _kv_quantize(v_w, cache["kv_codebook"])
        return cache | {
            "k_idx": write(cache["k_idx"], ki),
            "v_idx": write(cache["v_idx"], vi),
            "k_scale": write(cache["k_scale"], ks),
            "v_scale": write(cache["v_scale"], vs),
            "slot_pos": sp,
        }
    return cache | {
        "k": write(cache["k"], k_w.astype(cache["k"].dtype)),
        "v": write(cache["v"], v_w.astype(cache["v"].dtype)),
        "slot_pos": sp,
    }


def _cache_read(cache: dict, dtype):
    if "k_idx" in cache:
        book = cache["kv_codebook"]
        k = _kv_dequantize(cache["k_idx"], cache["k_scale"], book, dtype)
        v = _kv_dequantize(cache["v_idx"], cache["v_scale"], book, dtype)
        return k, v
    return cache["k"], cache["v"]


# ---------------------------------------------------------------------------
# paged KV cache (block pool + per-request block tables)
# ---------------------------------------------------------------------------

def _paged_kernel_default() -> bool:
    """REPRO_PAGED_KERNEL routing: opt-OUT on TPU, opt-in elsewhere.

    unset / "auto" -> kernel on TPU backends, jnp gather everywhere else
    (interpret-mode Pallas is far slower than XLA's fused gather on CPU);
    "0"/"off"/"false" -> always jnp; anything else -> always kernel.
    """
    env = os.environ.get("REPRO_PAGED_KERNEL", "auto").strip().lower()
    if env in ("", "auto"):
        return jax.default_backend() == "tpu"
    return env not in ("0", "off", "false")


# resolved on first paged-attention call, NOT at import: jax.default_backend()
# initializes the backend, which would break jax.distributed.initialize() /
# platform overrides in any program that merely imports the model stack.
# Tests monkeypatch this to force a route.
_USE_PAGED_KERNEL: bool | None = None


def _paged_kernel_enabled() -> bool:
    global _USE_PAGED_KERNEL
    if _USE_PAGED_KERNEL is None:
        _USE_PAGED_KERNEL = _paged_kernel_default()
    return _USE_PAGED_KERNEL


def init_paged_kv_cache(cfg, n_blocks: int, block_size: int, dtype,
                        quantized: bool = False) -> dict:
    """One attention layer's slice of the global paged block pool.

    Unlike the ring buffer, storage is a pool of ``n_blocks`` fixed-size
    token blocks shared by all requests; a per-request *block table*
    (attached per call by the serving scheduler) maps logical block
    ``pos // block_size`` to a pool slot. Token position ``p`` lives at
    ``(table[p // block_size], p % block_size)`` — no wraparound, blocks are
    allocated/freed as sequences grow/finish.

    quantized=True stores K/V as K-Means int4 indices (two per uint8) with a
    per-(token, head) fp32 scale — same format as the ring cache, kept
    packed in HBM and only expanded for the blocks a request actually reads.
    """
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    if not quantized:
        return {
            "pages_k": jnp.zeros((n_blocks, block_size, kv, hd), dtype),
            "pages_v": jnp.zeros((n_blocks, block_size, kv, hd), dtype),
        }
    from repro.models.model import _default_codebook  # structural codebook

    return {
        "pages_k_idx": jnp.zeros((n_blocks, block_size, kv, hd // 2), jnp.uint8),
        "pages_v_idx": jnp.zeros((n_blocks, block_size, kv, hd // 2), jnp.uint8),
        "pages_k_scale": jnp.zeros((n_blocks, block_size, kv, 1), jnp.float32),
        "pages_v_scale": jnp.zeros((n_blocks, block_size, kv, 1), jnp.float32),
        "kv_codebook": _default_codebook(4),
    }


def _paged_write(cache: dict, k, v, positions, ctx_lens):
    """Scatter this call's tokens into their block slots; returns new cache.

    positions: (B, S) absolute token positions; a token is written iff
    ``0 <= positions[b, s] < ctx_lens[b]`` and its block-table entry is
    allocated — padded rows (chunked-prefill tail, idle decode slots) carry
    positions outside that range and are dropped via an out-of-bounds
    scatter index, so an idle slot can never corrupt another request's block.
    """
    pages = cache["pages_k"] if "pages_k" in cache else cache["pages_k_idx"]
    n_blocks, bs = pages.shape[0], pages.shape[1]
    bt = cache["block_tables"]  # (B, max_blocks_per_seq)
    b, s = positions.shape
    blk = jnp.clip(positions // bs, 0, bt.shape[1] - 1)
    block_id = jnp.take_along_axis(bt, blk, axis=1)  # (B, S)
    valid = (positions >= 0) & (positions < ctx_lens[:, None]) & (block_id >= 0)
    dest = jnp.where(valid, block_id * bs + positions % bs, n_blocks * bs)

    def scatter(pool, vals):
        flat = pool.reshape(n_blocks * bs, *pool.shape[2:])
        flat = flat.at[dest.reshape(-1)].set(
            vals.reshape(b * s, *vals.shape[2:]), mode="drop"
        )
        return flat.reshape(pool.shape)

    if "pages_k_idx" in cache:
        ki, ks = _kv_quantize(k, cache["kv_codebook"])
        vi, vs = _kv_quantize(v, cache["kv_codebook"])
        return cache | {
            "pages_k_idx": scatter(cache["pages_k_idx"], ki),
            "pages_v_idx": scatter(cache["pages_v_idx"], vi),
            "pages_k_scale": scatter(cache["pages_k_scale"], ks),
            "pages_v_scale": scatter(cache["pages_v_scale"], vs),
        }
    return cache | {
        "pages_k": scatter(cache["pages_k"], k.astype(pages.dtype)),
        "pages_v": scatter(cache["pages_v"], v.astype(pages.dtype)),
    }


def _paged_attend(cache: dict, q, q_pos, softcap, window: int = 0):
    """Attention against the block pool through the block table.

    q: (B, S, KV, G, hd); q_pos: (B, S). Every batch row is a query *segment*
    of one sequence (decode: S == 1; chunked prefill: S == chunk; the packed
    token-budget step: B == n_tokens rows of S == 1). On TPU backends the
    Pallas gather kernel is the default route (REPRO_PAGED_KERNEL=0 opts
    out); elsewhere the jnp reference is used, which XLA fuses well and
    which lowers on any backend. ``window > 0`` masks keys at positions
    ``<= q_pos - window`` (sliding-window layers under the windowed_paged
    cache policy) — freed out-of-window table entries are < 0 and therefore
    never reachable through the surviving mask.
    """
    from repro.kernels import ref as kref

    bt, cl = cache["block_tables"], cache["ctx_lens"]
    quantized = "pages_k_idx" in cache
    # named unconditionally (telemetry-independent) so XLA profiles line up
    # with the serving timeline names in every mode — and the jaxpr is the
    # same whether telemetry is on or off
    with jax.named_scope("paged_attention"):
        if _paged_kernel_enabled():
            from repro.kernels.ops import should_interpret
            from repro.kernels.paged_attn import paged_attn_kernel_call

            if quantized:
                args = (cache["pages_k_idx"], cache["pages_k_scale"],
                        cache["pages_v_idx"], cache["pages_v_scale"],
                        cache["kv_codebook"])
            else:
                args = (cache["pages_k"], cache["pages_v"])
            o = paged_attn_kernel_call(
                q, *args, block_tables=bt, ctx_lens=cl, q_pos=q_pos,
                softcap=softcap, window=window, interpret=should_interpret(),
            )
            return o.astype(q.dtype)
        if quantized:
            return kref.paged_attn_quant_ref(
                q, cache["pages_k_idx"], cache["pages_k_scale"],
                cache["pages_v_idx"], cache["pages_v_scale"],
                cache["kv_codebook"], bt, cl, q_pos, softcap=softcap,
                window=window,
            ).astype(q.dtype)
        return kref.paged_attn_ref(
            q, cache["pages_k"], cache["pages_v"], bt, cl, q_pos,
            softcap=softcap, window=window,
        ).astype(q.dtype)


def attention_apply(
    p,
    x: jax.Array,
    cfg,
    *,
    positions: jax.Array,  # (S,) absolute positions of x's tokens
    cache: dict | None = None,  # ring-buffer cache (updated + returned)
    memory: jax.Array | None = None,  # cross-attention memory (B, M, d)
    window: int = 0,
    layer_tag: str = "attn",
):
    """GQA attention, all phases (train / prefill / decode / cross).

    Returns (out, new_cache). ``positions`` must be contiguous ascending per
    batch row: shape (S,) shared across the batch (train / prefill / ring
    decode), or (B, S) per-request (paged continuous-batching, where every
    row is at a different depth in its own sequence; position -1 marks a
    padded row that is neither written nor attended).

    Paged caches may carry ``token_slots`` (B,) — the packed token-budget
    layout, where ``block_tables``/ ``ctx_lens`` are per *slot* and each
    batch row is one SEGMENT (S contiguous tokens, possibly padded with
    position -1; S = 1 is the flat one-token-per-row case) of slot
    ``token_slots[b]``; the per-row table is gathered device-side, once per
    segment rather than once per token. Verify segments of the speculative
    decoder ride this same layout. Ring caches may carry ``pad_len`` (B,) —
    keys at positions < pad_len[b] (a left-padded prompt's pad tokens) are
    masked.
    """
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    softcap = cfg.logit_softcap
    paged = cache is not None and "block_tables" in cache

    q = constrain(dense_apply(p["wq"], x, f"{layer_tag}.q"), "batch", "seq", "heads_flat")
    q = q.reshape(b, s, kv, g, hd)
    kv_src = memory if memory is not None else x
    cross_cached = memory is not None and cache is not None and "ck" in cache
    if cross_cached:
        # decode: reuse the cross K/V computed once at prefill (recomputing
        # them per token cost 2 x M x d x kv x hd FLOPs PER LAYER PER TOKEN —
        # the vision decode cell's MODEL_FLOPS ratio was 0.04 before this)
        k, v = cache["ck"], cache["cv"]
    else:
        k = dense_apply(p["wk"], kv_src, f"{layer_tag}.k").reshape(b, -1, kv, hd)
        v = dense_apply(p["wv"], kv_src, f"{layer_tag}.v").reshape(b, -1, kv, hd)

    cross = memory is not None
    if not cross and cfg.pos_embed == "rope":
        q = rope_apply(q, positions, cfg.rope_theta)
        k = rope_apply(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "kv_heads", None, None)
    k = constrain(k, "batch", "seq" if not cross else None, "kv_heads", None)
    v = constrain(v, "batch", "seq" if not cross else None, "kv_heads", None)

    new_cache = cache
    if cross:
        if cache is not None and not cross_cached:
            # prefill populates the cross-KV cache for decode reuse
            new_cache = {"ck": k.astype(jnp.bfloat16), "cv": v.astype(jnp.bfloat16)}
        k_pos = jnp.zeros((k.shape[1],), jnp.int32)
        o = _attn_dispatch(q, k.astype(q.dtype), v.astype(q.dtype), positions, k_pos,
                           0, False, softcap, cfg)
    elif paged:
        if "token_slots" in cache:
            # packed layout: per-slot tables, one token per row — gather the
            # per-row table on device (host ships slots*max_blk ints, not T*)
            cache = cache | {
                "block_tables": jnp.take(cache["block_tables"],
                                         cache["token_slots"], axis=0)
            }
        q_pos = positions if positions.ndim == 2 else jnp.broadcast_to(positions, (b, s))
        new_cache = _paged_write(cache, k, v, q_pos, cache["ctx_lens"])
        o = _paged_attend(new_cache, q, q_pos, softcap, window)
    elif cache is not None:
        new_cache = _cache_write(cache, k, v, positions)
        ck, cv = _cache_read(new_cache, x.dtype)
        o = _attn_dispatch(
            q, ck, cv, positions, new_cache["slot_pos"], window, True, softcap, cfg,
            k_min=cache.get("pad_len"),
        )
    else:
        k_pos = positions
        o = _attn_dispatch(q, k, v, positions, k_pos, window, True, softcap, cfg)

    o = constrain(o.reshape(b, s, h * hd), "batch", "seq", "heads_flat")
    out = dense_apply(p["wo"], o, f"{layer_tag}.o")
    if "gate" in p:  # gated cross-attention (llama3.2-vision)
        out = jnp.tanh(p["gate"].astype(out.dtype)) * out
    return out, new_cache


def _attn_dispatch(q, k, v, q_pos, k_pos, window, causal, softcap, cfg, k_min=None):
    big = q.shape[1] * k.shape[1] > 4_194_304  # 2048^2
    if cfg.attn_chunk > 0 and big:
        return _sdpa_flash(
            q, k, v, q_pos, k_pos, window, causal, softcap,
            q_chunk=cfg.attn_chunk, k_chunk=cfg.attn_chunk, k_min=k_min,
        )
    return _sdpa_dense(q, k, v, q_pos, k_pos, window, causal, softcap, k_min=k_min)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, act_fn: str, dtype):
    k1, k2 = jax.random.split(key)
    mult = 2 if act_fn in ("silu", "gelu") else 1  # fused [gate; up]
    return {
        "wi": dense_init(k1, d, mult * d_ff, dtype),
        "wd": dense_init(k2, d_ff, d, dtype, scale=1.0 / math.sqrt(d_ff)),
    }


def mlp_apply(p, x: jax.Array, act_fn: str, layer_tag: str = "mlp") -> jax.Array:
    hidden = dense_apply(p["wi"], x, f"{layer_tag}.wi")
    if act_fn in ("silu", "gelu"):
        gate, up = jnp.split(hidden, 2, axis=-1)
        act = jax.nn.silu(gate) if act_fn == "silu" else jax.nn.gelu(gate)
        hidden = act * up
    elif act_fn == "relu2":
        hidden = jnp.square(jax.nn.relu(hidden))
    elif act_fn == "gelu_plain":
        hidden = jax.nn.gelu(hidden)
    else:
        raise ValueError(act_fn)
    hidden = constrain(hidden, "batch", "seq", "d_ff")
    return dense_apply(p["wd"], hidden, f"{layer_tag}.wd")
