"""Pallas TPU kernels: K-Means index GEMMs (the paper's LUT-GEMM on MXU).

TPU-native formulation of the Cartesian-product LUT GEMM, in two variants
sharing one tiling scheme:

* :func:`lut_gemm_kernel_call` — index-in, W4A4-style **nibble tier**
  (``nbits <= 4``: two 4-bit weight indices per byte) and the byte-packed
  **W5–W8 tier** (``byte_packed=True``: one index per byte). Per VMEM tile we
  unpack indices with integer bit ops, look centroids up on-chip, and feed
  the MXU with the dequantized tile, accumulating f32 partials across the K
  grid dimension.

* :func:`fused_lut_gemm_kernel_call` — **fused quantize+GEMM**: takes raw
  activations plus their per-token scale, bucketizes against the activation
  codebook's decision boundaries *inside the tile* (the Clustering-Unit
  sum-of-compares, same formulation as ``kernels/bucketize.py``), and
  immediately runs the index-GEMM. Activation indices exist only in VMEM —
  the separate quantize pass and its idx HBM roundtrip are gone. Given a
  per-token outlier mask it also folds in the outlier branch: marked
  channels enter the dot with their exact ``x/s`` instead of the centroid,
  so no weight row is gathered or unpacked outside the kernel.

Nibble planes. A packed byte holds output channels ``2i`` (low nibble) and
``2i+1`` (high nibble) — the format ``core.quantize.pack_int4`` owns. Rather
than interleaving the two nibble planes back along the lane axis (a minor-
dim relayout Mosaic does not lower), each plane is its own MXU dot into its
own output: the kernel emits the even and the odd output channels as two
lane-dense (M, N/2) arrays, and the wrapper interleaves them in XLA. A
``block_n`` tile therefore reads a (bk, block_n/2) packed block, so the
default ``block_n=256`` gives the 128-lane blocks the TPU tiling asks for.

Centroid lookup is ``core.quantize.lookup`` against codebook scalars held
in SMEM: a chain of 15 selects for a 16-entry codebook. The byte tier
selects ``book[16*hi + lo]`` as a chain over ``hi`` of chains over ``lo``;
every intermediate is a lane-dense (bk, bn) tile, so the VMEM working set
stays a few tiles wide.

No dequantized weight matrix ever exists in HBM — HBM traffic is the packed
index bytes plus <= 1 KiB of codebook, i.e. the paper's "no-dequantization"
property on the side that bounds TPU decode throughput.

Scales (per-token, per-out-channel) are rank-1 and applied by the wrapper in
``ops.py`` — keeping the kernels pure index-GEMMs keeps the LUT math testable
in isolation. M/N/K are all padded here (K via in-kernel masking of the
activation tile, so padded columns contribute exactly zero regardless of
what ``book[0]`` is).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import lookup

__all__ = ["lut_gemm_kernel_call", "fused_lut_gemm_kernel_call"]

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array, scalar reads


def mxu_precision():
    """Precision of the kernels' MXU dots, read at trace time.

    Kernel dots follow ``jax.default_matmul_precision`` as XLA's dots do:
    "highest"/"float32" asks Mosaic for full f32 passes; anything else keeps
    Mosaic's default (bf16 passes for f32 operands).
    """
    p = jax.config.jax_default_matmul_precision
    return jax.lax.Precision.HIGHEST if p in ("highest", "float32") else None


def _weight_planes(w_vals: jax.Array, w_book,
                   byte_packed: bool) -> tuple[jax.Array, ...]:
    """Dequantize one packed weight tile to its f32 output-channel planes:
    (bk, bn) for the byte tier, (even, odd) (bk, bn/2) for the nibble tier."""
    w = w_vals.astype(jnp.int32)
    if byte_packed:  # one index per byte
        return (lookup(w_book, w),)
    return lookup(w_book, w & 0xF), lookup(w_book, w >> 4)


def _accumulate(a, w_ref, w_book_ref, o_refs, *, byte_packed: bool):
    """o_refs[p] += a @ plane_p over the K grid axis (zeroed at kk == 0)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        for o_ref in o_refs:
            o_ref[...] = jnp.zeros_like(o_ref)

    planes = _weight_planes(w_ref[...], w_book_ref, byte_packed)
    for o_ref, w in zip(o_refs, planes):
        o_ref[...] += jnp.dot(a, w, precision=mxu_precision(),
                              preferred_element_type=jnp.float32)


def _mask_padded_k(a: jax.Array, block_k: int, k_true: int) -> jax.Array:
    """Zero activation columns past the true K (padded-K tiles only).

    Zeroing the activation side is sufficient: the padded weight rows then
    multiply exact zeros, so the pad index value (0 -> book[0] != 0) never
    leaks into the accumulator.
    """
    col = pl.program_id(2) * block_k + jax.lax.broadcasted_iota(
        jnp.int32, a.shape, 1
    )
    return jnp.where(col < k_true, a, 0.0)


def _index_kernel(a_idx_ref, w_ref, a_book_ref, w_book_ref, *o_refs,
                  byte_packed: bool, block_k: int,
                  k_true: int, masked_k: bool):
    """Grid: (M/bm, N/bn, K/bk); K is the innermost (arbitrary) dimension."""
    a = lookup(a_book_ref, a_idx_ref[...])  # (bm, bk) f32
    if masked_k:
        a = _mask_padded_k(a, block_k, k_true)
    _accumulate(a, w_ref, w_book_ref, o_refs, byte_packed=byte_packed)


def _fused_kernel(*refs, byte_packed: bool, mul_form: bool, fold: bool,
                  block_k: int, k_true: int, masked_k: bool):
    """Bucketize-then-GEMM in one pass: activation indices never leave VMEM.

    refs: x, s, [outlier mask when ``fold``], w, bounds, a_book, w_book,
    then the output planes.

    ``mul_form`` selects the compare formulation so indices are bit-identical
    to ``core.quantize.quantize_activation`` for the matching input dtype:
    f32 compares ``x/s >= b_i`` (the searchsorted path), bf16 compares
    ``x >= s*b_i`` (the fused sum-of-compares path).

    ``fold`` is the outlier branch in the tile (detect-then-split): where the
    (bm, bk) outlier mask is set, the activation is the exact ``x/s`` instead
    of its centroid, so the one dot is main branch plus compensation.
    """
    x_ref, s_ref = refs[:2]
    w_ref, bounds_ref, a_book_ref, w_book_ref, *o_refs = refs[2 + fold:]
    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    s = s_ref[...].astype(jnp.float32)  # (bm, 1) per-token scale
    idx = jnp.zeros(x.shape, jnp.int32)
    if mul_form:
        for i in range(bounds_ref.shape[0]):
            idx += (x >= s * bounds_ref[i]).astype(jnp.int32)
    else:
        xn = x / s
        for i in range(bounds_ref.shape[0]):
            idx += (xn >= bounds_ref[i]).astype(jnp.int32)

    a = lookup(a_book_ref, idx)
    if fold:
        a = jnp.where(refs[2][...] != 0, x / s if mul_form else xn, a)
    if masked_k:
        a = _mask_padded_k(a, block_k, k_true)
    _accumulate(a, w_ref, w_book_ref, o_refs, byte_packed=byte_packed)


def _grid_geometry(m: int, n: int, k: int, block_m: int | None,
                   block_n: int | None, block_k: int | None,
                   byte_packed: bool):
    """Clamp block sizes and compute padded grid extents.

    ``block_n`` counts logical output channels for both tiers, so a nibble
    tile reads a (bk, bn/2) packed block. Byte tiers default to a smaller K
    block: the two-level select chain keeps a few more (bk, bn) tiles live.

    VMEM working set per step (nibble defaults, W4A4):
      a_idx 128x512 int32 = 256 KiB, w 512x128 uint8 = 64 KiB (int32 copy
      256 KiB), two planes 512x128 f32 = 512 KiB, acc 2x 128x128 f32 = 128 KiB
    -> ~1.2 MiB, comfortable with double-buffering.
    """
    bm = min(block_m or 128, m)
    bn = min(block_n or (128 if byte_packed else 256), n)
    bk = min(block_k or (256 if byte_packed else 512), k)
    if not byte_packed and bn % 2:
        raise ValueError("block_n must be even (nibble packing)")
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    grid = ((m + pm) // bm, (n + pn) // bn, (k + pk) // bk)
    return bm, bn, bk, pm, pn, pk, grid


def _index_gemm_call(kernel, geometry, row_args, w_packed, books, *, m: int,
                     n: int, k: int, byte_packed: bool, interpret: bool,
                     **kernel_kw):
    """Shared pallas_call plumbing for both index-GEMM variants.

    ``row_args`` are the activation-side inputs, already padded to the grid:
    (M, K) tiles or (M, 1) per-token columns. ``books`` are the scalar
    tables that go to SMEM after the weights (boundaries, codebooks).
    """
    bm, bn, bk, pm, pn, pk, grid = geometry
    w_packed = jnp.pad(w_packed, ((0, pk), (0, pn if byte_packed else pn // 2)))
    wn = bn if byte_packed else bn // 2  # packed block width == plane width
    n_planes = 1 if byte_packed else 2
    row_specs = [pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0))
                 if a.shape[1] == 1 else
                 pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
                 for a in row_args]
    outs = pl.pallas_call(
        functools.partial(kernel, byte_packed=byte_packed, block_k=bk,
                          k_true=k, masked_k=pk > 0, **kernel_kw),
        grid=grid,
        in_specs=row_specs
        + [pl.BlockSpec((bk, wn), lambda i, j, kk: (kk, j))]
        + [_SMEM] * len(books),
        out_specs=[pl.BlockSpec((bm, wn), lambda i, j, kk: (i, j))] * n_planes,
        out_shape=[jax.ShapeDtypeStruct((m + pm, (n + pn) // n_planes),
                                        jnp.float32)] * n_planes,
        interpret=interpret,
    )(*row_args, w_packed, *books)
    # nibble planes hold the even / odd output channels: interleave in XLA
    y = outs[0] if byte_packed else jnp.stack(outs, axis=-1).reshape(m + pm, -1)
    return y[:m, :n]


def lut_gemm_kernel_call(
    a_idx: jax.Array,  # (M, K) int32 activation codebook indices
    w_packed: jax.Array,  # nibble: (K, N//2) uint8; byte: (K, N) uint8
    a_book: jax.Array,  # (2^nA,) f32
    w_book: jax.Array,  # (2^nW,) f32
    *,
    byte_packed: bool = False,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    """Tiled index-GEMM pallas_call; M, N and K are all padded here.

    Returns the unscaled (M, N) f32 index-GEMM
    ``Y[m,n] = sum_k aBook[aIdx[m,k]] * wBook[wIdx[k,n]]``.
    """
    m, k = a_idx.shape
    n = w_packed.shape[1] * (1 if byte_packed else 2)
    geo = _grid_geometry(m, n, k, block_m, block_n, block_k, byte_packed)
    pm, pk = geo[3], geo[5]
    return _index_gemm_call(
        _index_kernel, geo, [jnp.pad(a_idx, ((0, pm), (0, pk)))], w_packed,
        [a_book.astype(jnp.float32), w_book.astype(jnp.float32)],
        m=m, n=n, k=k, byte_packed=byte_packed, interpret=interpret,
    )


def fused_lut_gemm_kernel_call(
    x: jax.Array,  # (M, K) raw activations (f32 or bf16)
    scale: jax.Array,  # (M, 1) f32 per-token scale (full-K reduction, rank-1)
    w_packed: jax.Array,  # nibble: (K, N//2) uint8; byte: (K, N) uint8
    bounds: jax.Array,  # (2^nA - 1,) f32 activation decision boundaries
    a_book: jax.Array,  # (2^nA,) f32
    w_book: jax.Array,  # (2^nW,) f32
    *,
    byte_packed: bool = False,
    mul_form: bool = False,
    outlier_mask: jax.Array | None = None,  # (M, K) 32-bit, 1 at outliers
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    """Fused activation-quantize + index-GEMM (unscaled (M, N) f32 output).

    The per-token scale needs a full-K reduction so it is computed by the
    caller (a rank-1 pass XLA fuses); everything O(M*K) — bucketize, index,
    centroid lookup — happens inside the tile. Padded rows must carry a
    nonzero ``scale`` (this wrapper pads with ones) so the in-kernel
    division stays NaN-free; padded rows are sliced off regardless.

    ``outlier_mask`` folds the outlier branch into the tile: at its nonzero
    entries the activation is the exact ``x/s``, elsewhere the centroid, so
    the result is ``sum_k a[m,k] * wBook[wIdx[k,n]]`` with both branches in
    ``a``. It is tiled like ``x``; a 32-bit type keeps the (8, 128) tiling.
    """
    m, k = x.shape
    n = w_packed.shape[1] * (1 if byte_packed else 2)
    geo = _grid_geometry(m, n, k, block_m, block_n, block_k, byte_packed)
    pm, pk = geo[3], geo[5]
    s = jnp.pad(scale.astype(jnp.float32), ((0, pm), (0, 0)),
                constant_values=1.0)
    rows = [jnp.pad(x, ((0, pm), (0, pk))), s]
    if outlier_mask is not None:
        rows.append(jnp.pad(outlier_mask, ((0, pm), (0, pk))))
    return _index_gemm_call(
        _fused_kernel, geo, rows, w_packed,
        [bounds.astype(jnp.float32), a_book.astype(jnp.float32),
         w_book.astype(jnp.float32)],
        m=m, n=n, k=k, byte_packed=byte_packed, interpret=interpret,
        mul_form=mul_form, fold=outlier_mask is not None,
    )
