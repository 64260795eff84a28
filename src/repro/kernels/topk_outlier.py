"""Pallas TPU kernel: Orizuru — dual top-k/bottom-k outlier detection (§IV-D).

The ASIC Orizuru is a two-fold tournament tree (max tree + min tree) with
SHARED LEAF COMPARISONS: the N/2 pairwise compares that initialize the max
tree's first level are reused (reversed) for the min tree, giving
1.5N + 2k·log2(N) comparisons instead of ~3N (or 6N for SpAtten's engine).

TPU adaptation: the serial pop-one-per-cycle loop is an ASIC latency trick
with no TPU analogue — a vectorized max over a vreg-resident array has
O(log N) depth anyway. What we keep is the *shared-pairwise* trick and the
*pair-collapse* structure:

  phase 1 (shared): A = max(x_lo_half, x_hi_half), B = min(...)
                    — N/2 compares produce level-1 of BOTH trees;
  phase 2 (pop):    k iterations of max over the N/2-wide A-array; a popped
                    pair falls back to its other leaf (B) and then to -inf —
                    exactly the paper's tree-maintenance semantics, k·(N/2)
                    vector-lanes of work but only k sequential steps;
  min side:         the SAME pop routine on (-B, -A) — comparisons reused.

Pairs are lane ``j`` with lane ``j + N/2``: both halves are lane-aligned
slices, where pairing neighbours would need a stride-2 lane shuffle. N is
padded to a multiple of 256 with lanes that are −inf on the max side and
+inf on the min side, so a pad can never be selected while k <= N real
values remain (indices therefore never point at a pad). All reductions run
in f32 (lane indices are exact there), the one type Mosaic reduces.

Tie-breaking: the left child (lower index) wins within a pair, and among
equal pair fronts the lowest original index is popped first, which
reproduces lax.top_k's ascending-index order on equal values (asserted in
tests against the sort-based oracle, including duplicate-heavy and
all-equal inputs).

``streaming_quantize_outlier_kernel_call`` is the serving decode form: one
pass over the (bm, N) tile emits the bucketized activation indices AND the
per-token outlier set, so dynamic detection adds no extra HBM roundtrip on
top of activation quantization (the tile is read once).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["topk_outlier_kernel_call", "streaming_quantize_outlier_kernel_call"]

_NEG_INF = float("-inf")  # plain literal: jnp constants would be captured consts in the kernel
_POS_INF = float("inf")
_BIG = float(2**30)  # above every lane index, exact in f32
_LANE_PAIRS = 256  # N pads to this so both pair halves are 128-lane aligned


def _default_interpret(interpret: bool | None) -> bool:
    # mirrors ops.should_interpret(); kept local to avoid a kernels->ops cycle
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _pop_topk(cur, fallback, idx_cur, idx_fb, k: int):
    """k pops from a pair-collapsed array with single-fallback maintenance.

    cur      : (bm, P) current per-pair front value (pair maxima)
    fallback : (bm, P) the other leaf of each pair
    idx_cur/idx_fb : original column indices of cur/fallback entries (f32)
    Returns (vals (bm, k) descending, idx (bm, k) f32).
    """
    bm, p = cur.shape
    col_k = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)
    state = jnp.zeros((bm, p), jnp.float32)  # pops taken from each pair
    vals = jnp.full((bm, k), _NEG_INF)
    idxs = jnp.zeros((bm, k), jnp.float32)

    def body(t, carry):
        cur, idx_cur, state, vals, idxs = carry
        v = jnp.max(cur, axis=1, keepdims=True)  # (bm, 1)
        # lowest original index among the maxima (lax.top_k's tie order);
        # live fronts hold distinct leaves, so exactly one pair is hit
        orig = jnp.min(jnp.where(cur == v, idx_cur, _BIG), axis=1,
                       keepdims=True)
        hit = idx_cur == orig
        fresh = state == 0.0
        cur = jnp.where(hit, jnp.where(fresh, fallback, _NEG_INF), cur)
        idx_cur = jnp.where(hit & fresh, idx_fb, idx_cur)
        state = state + hit.astype(jnp.float32)
        write = col_k == t
        vals = jnp.where(write, v, vals)
        idxs = jnp.where(write, orig, idxs)
        return cur, idx_cur, state, vals, idxs

    carry = (cur, idx_cur, state, vals, idxs)
    _, _, _, vals, idxs = jax.lax.fori_loop(0, k, body, carry)
    return vals, idxs


def _dual_topk(x, k: int, n_valid: int):
    """Shared-pairwise dual top-k/bottom-k over a (bm, n) f32 tile.

    ``n_valid`` < n means the trailing lanes are padding: they become −inf on
    the max side and +inf on the min side, so with k <= n_valid and finite
    real data a pad lane is never popped (its fallback is the sign-flipped
    pad, i.e. worse than any real value on either side). With n_valid == n
    both trees read the SAME array and the pairwise comparisons are shared.
    Returns (hi_v desc, hi_i, lo_v asc, lo_i); indices as int32.
    """
    bm, n = x.shape
    h = n // 2
    if n_valid < n:
        col = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
        x_hi = jnp.where(col < n_valid, x, _NEG_INF)
        x_lo = jnp.where(col < n_valid, x, _POS_INF)
    else:
        x_hi = x_lo = x

    left = jax.lax.broadcasted_iota(jnp.int32, (bm, h), 1).astype(jnp.float32)
    right = left + float(h)

    def level1(xs, right_wins):
        """Pair fronts, fallbacks and their leaf indices for one tree."""
        xl, xr = xs[:, :h], xs[:, h:]
        rw = right_wins(xl, xr)  # strict: ties go left (paper's rule)
        return (jnp.where(rw, xr, xl), jnp.where(rw, xl, xr),
                jnp.where(rw, right, left), jnp.where(rw, left, right))

    # --- shared pairwise comparisons (level-1 of both trees): N/2 compares.
    # Each tree keeps its own leaf mask (paper: m^(p) vs m^(q)), so primary
    # and fallback indices are complements PER TREE — on a tie both trees
    # pick the left child first and fall back to the right one.
    a, b, a_idx, a_fb_idx = level1(x_hi, lambda xl, xr: xr > xl)
    c, d, c_idx, c_fb_idx = level1(x_lo, lambda xl, xr: xr < xl)

    hi_v, hi_i = _pop_topk(a, b, a_idx, a_fb_idx, k)
    neg_v, lo_i = _pop_topk(-c, -d, c_idx, c_fb_idx, k)
    return hi_v, hi_i.astype(jnp.int32), -neg_v, lo_i.astype(jnp.int32)


def _kernel(x_ref, hi_v_ref, hi_i_ref, lo_v_ref, lo_i_ref, *, k: int,
            n_valid: int):
    hi_v, hi_i, lo_v, lo_i = _dual_topk(x_ref[...], k, n_valid)
    hi_v_ref[...] = hi_v
    hi_i_ref[...] = hi_i
    lo_v_ref[...] = lo_v
    lo_i_ref[...] = lo_i


def _streaming_kernel(x_ref, s_ref, b_ref, idx_ref, hi_v_ref, hi_i_ref,
                      lo_v_ref, lo_i_ref, *, k: int, n_valid: int,
                      n_boundaries: int, mul_form: bool):
    """Bucketize + dual top-k in ONE tile read (the Orizuru streaming form).

    Index selection is bit-identical to ``quantize_activation``: mul_form
    (bf16 origin) compares x >= s*b_i, f32 form counts (x/s) >= b_i — the
    same rank searchsorted computes. Detection runs on the raw (unscaled)
    f32 activations, exactly what the unfused path hands to lax.top_k.
    """
    x = x_ref[...]  # (bm, n) f32
    s = s_ref[...]  # (bm, 1) f32
    idx = jnp.zeros(x.shape, jnp.int32)
    if mul_form:
        for i in range(n_boundaries):
            idx += (x >= s * b_ref[i]).astype(jnp.int32)
    else:
        xd = x / s
        for i in range(n_boundaries):
            idx += (xd >= b_ref[i]).astype(jnp.int32)
    idx_ref[...] = idx
    hi_v, hi_i, lo_v, lo_i = _dual_topk(x, k, n_valid)
    hi_v_ref[...] = hi_v
    hi_i_ref[...] = hi_i
    lo_v_ref[...] = lo_v
    lo_i_ref[...] = lo_i


def _pad_args(x: jax.Array, k: int, block_m: int):
    """Shared shape plumbing: pad N to whole lane pairs and M to a block
    multiple.

    Returns (x padded f32, bm, grid_m, mp (padded rows), n_valid, np (padded
    cols)). Pad lanes are zero here; the kernel masks them to ±inf per side.
    """
    m, n = x.shape
    if not 1 <= k <= n:
        raise ValueError(f"k={k} must be in [1, N={n}]")
    pn = (-n) % _LANE_PAIRS
    bm = min(block_m, m)
    pm = (-m) % bm
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x.astype(jnp.float32), bm, (m + pm) // bm, m + pm, n, n + pn


def topk_outlier_kernel_call(
    x: jax.Array,  # (M, N) f32
    k: int,
    *,
    block_m: int = 8,
    interpret: bool | None = None,
):
    """Returns (hi_vals desc, hi_idx, lo_vals asc, lo_idx), each (M, k).

    ``interpret=None`` auto-selects interpret mode off-TPU.
    """
    m = x.shape[0]
    x, bm, gm, mp, n_valid, n = _pad_args(x, k, block_m)
    shp = jax.ShapeDtypeStruct((mp, k), jnp.float32)
    shpi = jax.ShapeDtypeStruct((mp, k), jnp.int32)
    outs = pl.pallas_call(
        functools.partial(_kernel, k=k, n_valid=n_valid),
        grid=(gm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))] * 4,
        out_shape=[shp, shpi, shp, shpi],
        interpret=_default_interpret(interpret),
    )(x)
    return tuple(o[:m] for o in outs)


def streaming_quantize_outlier_kernel_call(
    x: jax.Array,  # (M, N) f32 raw activations
    scale: jax.Array,  # (M, 1) f32 per-token scale, computed by the caller
    boundaries: jax.Array,  # (2^n - 1,) f32 sorted codebook boundaries
    k: int,
    *,
    mul_form: bool = False,
    block_m: int = 8,
    interpret: bool | None = None,
):
    """Fused quantize + detect: (idx (M, N) i32, hi_v, hi_i, lo_v, lo_i).

    The scale comes IN (same contract as the fused LUT-GEMM kernel) so the
    per-token scale is bit-identical to ``token_scale`` however it is
    consumed downstream.
    """
    m = x.shape[0]
    x, bm, gm, mp, n_valid, n = _pad_args(x, k, block_m)
    if scale.shape != (m, 1):
        raise ValueError(f"scale must be (M, 1) = ({m}, 1), got {scale.shape}")
    s = scale.astype(jnp.float32)
    if mp > m:
        # pad scales with ones: pad-row divisions stay finite, rows are cut
        s = jnp.concatenate([s, jnp.ones((mp - m, 1), jnp.float32)])
    shp = jax.ShapeDtypeStruct((mp, k), jnp.float32)
    shpi = jax.ShapeDtypeStruct((mp, k), jnp.int32)
    outs = pl.pallas_call(
        functools.partial(
            _streaming_kernel, k=k, n_valid=n_valid,
            n_boundaries=int(boundaries.shape[0]), mul_form=mul_form,
        ),
        grid=(gm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))]
        + [pl.BlockSpec((bm, k), lambda i: (i, 0))] * 4,
        out_shape=[jax.ShapeDtypeStruct((mp, n), jnp.int32), shp, shpi, shp, shpi],
        interpret=_default_interpret(interpret),
    )(x, s, boundaries.astype(jnp.float32))
    idx, hi_v, hi_i, lo_v, lo_i = outs
    return (idx[:m, :n_valid], hi_v[:m], hi_i[:m], lo_v[:m], lo_i[:m])
