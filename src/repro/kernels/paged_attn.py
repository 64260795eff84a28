"""Pallas TPU kernel: paged-attention gather for continuous-batching serving.

A query *segment* per sequence attends to that sequence's KV blocks through a
block table (vLLM-style paged KV cache). The segment generalizes the
original 1-token decode contract: decode is S == 1, a chunked-prefill slice
is S == chunk, and the packed token-budget step runs B == token_budget rows
of S == 1 (each row is one token with its own table). The kernel is the
decode-side analogue of lut_gemm's no-dequantization property:

  1. the grid is (sequence, block); the *block table is scalar-prefetched* so
     each step's BlockSpec index_map DMAs exactly the pool block the sequence
     owns — non-resident blocks are never touched,
  2. int4 K-Means blocks are unpacked (VPU bit ops) and dequantized via the
     16-way compare-select LUT *in VMEM*; HBM traffic stays bs x kv x hd / 2
     bytes of indices + scales per block,
  3. softmax runs online (flash-style) across a sequence's blocks in f32
     scratch, so per-step VMEM is one block x one segment, not the whole
     context.

Layout inside the kernel is 2-D throughout, which is what Mosaic lowers: a
pool block is viewed as (bs, KV*hd) — all heads side by side on the lanes —
and the wrapper hands the queries in *block-diagonal* form, one row per
(segment position, kv head, group member) holding its head's query in that
head's lanes and zeros elsewhere. One (rows, KV*hd) x (KV*hd, bs) MXU dot
then yields every head's scores, and (rows, bs) x (bs, KV*hd) yields every
row's value mix; the wrapper keeps each row's own head (the diagonal). The
int4 pools pack hd positions ``2i``/``2i+1`` into one byte (``pack_int4``),
so queries and outputs are split into even and odd planes instead of
interleaving nibbles in VMEM, and the per-(token, head) scales reach the
rows through a one-hot head selector.

Contract (both variants): q (B, S, KV, G, hd); q_pos (B, S) int32 absolute
query positions (< 0 = padded row, fully masked); block_tables (B, max_blk)
int32 with entries < 0 meaning unallocated (masked out via ctx_lens);
ctx_lens (B,) valid context length. Output (B, S, KV, G, hd) f32. Oracles:
``ref.paged_attn_ref`` / ``ref.paged_attn_quant_ref`` (same layout).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import lookup
from repro.kernels.lut_gemm import mxu_precision

__all__ = ["paged_attn_kernel_call"]

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_HIGHEST = jax.lax.Precision.HIGHEST  # one-hot selector dots stay exact


def _dot_t(a, b, precision=None):
    """a (R, D) x b (T, D) -> (R, T): contract the lane axes of both."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _attend(q_planes, k_planes, v_planes, k_scale, v_scale, cl, qp, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, hd: int, max_blk: int,
            softcap: float, window: int):
    """One online-softmax step of every row against one pool block.

    q_planes: (R, Dp) query planes; k_planes/v_planes: (bs, Dp) f32 block
    planes; k_scale/v_scale: (R, bs) per-row scales or None; qp: (R, 1)
    absolute query positions. ``window > 0`` (static) adds the sliding-
    window mask term — keys at ``<= qp - window`` are dead, matching the
    ring cache's ``_mask``.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    prec = mxu_precision()
    s = _dot_t(q_planes[0], k_planes[0], prec)
    for q_plane, k_plane in zip(q_planes[1:], k_planes[1:]):
        s = s + _dot_t(q_plane, k_plane, prec)
    if k_scale is not None:
        s = s * k_scale
    s = s * (hd ** -0.5)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = (kpos < cl) & (kpos <= qp)
    if window > 0:
        valid &= kpos > qp - window
    s = jnp.where(valid, s, _NEG_INF)
    m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)  # (R, bs)
    alpha = jnp.exp(m_ref[...] - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    pv = p if v_scale is None else p * v_scale
    for i, vp in enumerate(v_planes):
        acc_ref[i] = acc_ref[i] * alpha + jnp.dot(
            pv, vp, precision=prec, preferred_element_type=jnp.float32)

    @pl.when(j == max_blk - 1)
    def _done():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        for i in range(len(v_planes)):
            o_ref[0, i] = acc_ref[i] * inv


def _kernel_bf16(bt_ref, cl_ref, qp_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, **kw):
    _attend([q_ref[0, 0]], [k_ref[0].astype(jnp.float32)],
            [v_ref[0].astype(jnp.float32)], None, None,
            cl_ref[pl.program_id(0)], qp_ref[0], o_ref, m_ref, l_ref, acc_ref,
            **kw)


def _nibble_planes(idx, book):
    """(bs, KV*hd/2) packed uint8 -> even / odd hd-position f32 planes."""
    idx = idx.astype(jnp.int32)
    return [lookup(book, idx & 0xF), lookup(book, idx >> 4)]


def _kernel_quant(bt_ref, cl_ref, qp_ref, sel_ref, q_ref, ki_ref, ks_ref,
                  vi_ref, vs_ref, book_ref, o_ref, m_ref, l_ref, acc_ref, **kw):
    sel = sel_ref[...]  # (R, KV) one-hot: the kv head of each row
    _attend([q_ref[0, 0], q_ref[0, 1]],
            _nibble_planes(ki_ref[0], book_ref),  # dequantized in VMEM only
            _nibble_planes(vi_ref[0], book_ref),
            _dot_t(sel, ks_ref[0], _HIGHEST), _dot_t(sel, vs_ref[0], _HIGHEST),
            cl_ref[pl.program_id(0)], qp_ref[0], o_ref, m_ref, l_ref, acc_ref,
            **kw)


def _block_diag(q: jax.Array) -> jax.Array:
    """(B, S, KV, G, d) -> (B, S*KV*G, KV*d): row (s, h, g) holds q[s, h, g]
    in lanes [h*d, (h+1)*d) and exact zeros elsewhere."""
    b, sq, kv, g, d = q.shape
    eye = jnp.eye(kv, dtype=jnp.float32)
    bd = q.astype(jnp.float32)[..., None, :] * eye[:, None, :, None]
    return bd.reshape(b, sq * kv * g, kv * d)


def paged_attn_kernel_call(
    q: jax.Array,  # (B, S, KV, G, hd) — a query segment per sequence
    *storage: jax.Array,  # (k_pages, v_pages) | (k_idx, k_scale, v_idx, v_scale, book)
    block_tables: jax.Array,  # (B, max_blk) int32
    ctx_lens: jax.Array,  # (B,) int32
    q_pos: jax.Array,  # (B, S) int32 absolute positions; < 0 = padded row
    softcap: float = 0.0,
    window: int = 0,  # static sliding window; 0 = full causal attention
    interpret: bool = True,
) -> jax.Array:
    """Segmented paged decode/prefill attention; see module docstring."""
    b, sq, kv, g, hd = q.shape
    max_blk = block_tables.shape[1]
    quantized = len(storage) == 5
    if not quantized and len(storage) != 2:
        raise ValueError(f"expected 2 (bf16) or 5 (int4) storage arrays, got {len(storage)}")
    n_blocks, bs = storage[0].shape[:2]
    rows = sq * kv * g
    # entries < 0 are unallocated: clamp for the DMA, mask via ctx_lens/q_pos
    bt_flat = jnp.clip(block_tables, 0, n_blocks - 1).reshape(-1)
    qp_rows = jnp.broadcast_to(q_pos.astype(jnp.int32)[:, :, None, None],
                               (b, sq, kv, g)).reshape(b, rows, 1)
    lanes = lambda a: a.reshape(n_blocks, bs, -1)  # heads side by side

    def pool_spec(width):
        return pl.BlockSpec(
            (1, bs, width),
            lambda bi, j, bt, cl, _mb=max_blk: (bt[bi * _mb + j], 0, 0))

    def full_spec(shape):
        return pl.BlockSpec(shape, lambda bi, j, bt, cl: (0,) * len(shape))

    def row_spec(shape):
        return pl.BlockSpec((1, *shape),
                            lambda bi, j, bt, cl: (bi,) + (0,) * len(shape))

    if quantized:
        k_idx, k_scale, v_idx, v_scale, book = storage
        width = kv * hd // 2
        planes = [_block_diag(q[..., 0::2]), _block_diag(q[..., 1::2])]
        sel = jnp.broadcast_to(jnp.eye(kv, dtype=jnp.float32)[None, :, None],
                               (sq, kv, g, kv)).reshape(rows, kv)
        kernel = _kernel_quant
        extra_specs, extra_args = [full_spec((rows, kv))], [sel]
        pool_specs = [pool_spec(width), pool_spec(kv), pool_spec(width),
                      pool_spec(kv), pl.BlockSpec(memory_space=pltpu.SMEM)]
        pool_args = [lanes(k_idx), lanes(k_scale), lanes(v_idx),
                     lanes(v_scale), book.astype(jnp.float32)]
    else:
        width = kv * hd
        planes = [_block_diag(q)]
        kernel = _kernel_bf16
        extra_specs, extra_args = [], []
        pool_specs = [pool_spec(width), pool_spec(width)]
        pool_args = [lanes(a) for a in storage]
    n_planes = len(planes)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_blk),
        in_specs=[row_spec((rows, 1))] + extra_specs
        + [row_spec((n_planes, rows, width))] + pool_specs,
        out_specs=row_spec((n_planes, rows, width)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),  # running max
            pltpu.VMEM((rows, 1), jnp.float32),  # running denominator
            pltpu.VMEM((n_planes, rows, width), jnp.float32),  # accumulator
        ],
    )
    o = pl.pallas_call(
        functools.partial(kernel, bs=bs, hd=hd, max_blk=max_blk,
                          softcap=softcap, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_planes, rows, width), jnp.float32),
        interpret=interpret,
    )(bt_flat, ctx_lens, qp_rows, *extra_args, jnp.stack(planes, axis=1),
      *pool_args)
    # re-interleave the even / odd hd planes, then keep each row's own head
    o = jnp.moveaxis(o, 1, -1).reshape(b, sq, kv, g, kv, hd)
    return jnp.moveaxis(jnp.diagonal(o, axis1=2, axis2=4), -1, 2)
