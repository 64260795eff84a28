"""Seeded weights for a served configuration, made on the device one layer
at a time, in the dtype they are served in.

Every leaf is drawn from its own key, ``fold_in(fold_in(key(seed),
crc32(path)), layer)``: the path is the leaf's place in the parameter tree
without a layer index (``blocks/attn/wq/w``), and ``layer`` is the block's
index (0 for the leaves outside the blocks). So block ``l`` is the same
whatever the depth of the stack and whichever other layers are made, and
the harness can make, quantize and free one block at a time, and the
reference can make each again after the window: the bf16 tree is never
whole on the device.

Every projection that the configuration quantizes is drawn on the grid its
K-Means quantizer can represent: per output channel a scale, times one of
``2**bits`` evenly spaced levels in [-1, 1], each level equally likely,
with both end levels placed once in every output channel at rows drawn
from the seed, so that the channel's absolute maximum is its scale even
where the input width is small against ``2**bits``. The quantizer's
quantile initialisation then lands on the levels and Lloyd's iterations
keep them, so weight quantization is lossless up to bf16 rounding. The
correctness check compares the served tokens with a float32 reference of
these same bf16 weights: what it measures is the serving path (activation
quantization, Orizuru compensation, the int4 paged KV cache, every
kernel), not weight rounding that no later change can remove. A control
one bit lower cannot represent the grid and shows. Routed experts are drawn
so too, one scale per (expert, output channel), whether the tree holds them
as raw ``(E, k, n)`` stacks (``moe/wi``, the program's ``moe`` family
today) or as ``{"w": (E, k, n)}`` projections (the form its quantizer
takes, for a program that quantizes routed experts); the router and the
shared expert's gate are float32 normals of variance ``1/d``.

Output projections (``wo``, ``wd``) are scaled by ``1/sqrt(2 * layers)`` so
that the residual stream stays dominated by no single block; random weights
without it give a stack whose logits swing with one-ulp input changes.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.discover import QUANT_LEAVES, resolve_bits

EXPERT_STACKS = ("wi", "wd")  # raw (E, k, n) expert stacks under "moe"


def _path(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in keypath)


def grid_levels(bits: int) -> tuple[float, float]:
    """(spacing, rms) of ``2**bits`` evenly spaced levels on [-1, 1]."""
    n = 2 ** bits
    return 2.0 / (n - 1), math.sqrt((n + 1) / (3.0 * (n - 1)))


def _on_grid(key, shape, bits: int, gain: float):
    """Levels of ``2**bits`` on [-1, 1] times a lognormal scale per output
    channel (per leading index and last axis), both end levels in every
    channel."""
    kb, ks, k1, k2 = jax.random.split(key, 4)
    top = 2 ** bits - 1
    idx = (jax.random.bits(kb, shape, jnp.uint8) & top).astype(jnp.int32)
    k_in = shape[-2]
    chan = shape[:-2] + (1, shape[-1])
    hi = jax.random.randint(k1, chan, 0, k_in)
    lo = (hi + jax.random.randint(k2, chan, 1, k_in)) % k_in
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
    idx = jnp.where(rows == hi, top, jnp.where(rows == lo, 0, idx))
    col = gain * jnp.exp(0.25 * jax.random.normal(ks, chan, jnp.float32))
    return (2.0 * idx.astype(jnp.float32) - top) / top * col


def _leaf(key, path: str, shape, dtype, cfg: dict):
    leaf = path.rsplit("/", 2)
    d = cfg["d_model"]
    if path.endswith("embed/table"):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)
    if path.endswith("head/w") or path.endswith(("router/w", "shared_gate/w")):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(d)).astype(dtype)
    if leaf[-1] == "scale" and leaf[-2].startswith("norm"):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if leaf[-1] == "bias" and leaf[-2].startswith("norm"):
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    raw_experts = leaf[-2] == "moe" and leaf[-1] in EXPERT_STACKS
    if raw_experts or (leaf[-1] == "w" and leaf[-2] in QUANT_LEAVES):
        name = leaf[-1] if raw_experts else leaf[-2]
        bits = resolve_bits(cfg["quant"], path if raw_experts else path.rsplit("/", 1)[0])
        gain = 1.0 / (math.sqrt(shape[-2]) * grid_levels(bits)[1])
        if name in ("wo", "wd"):
            gain /= math.sqrt(2 * cfg["n_layers"])
        if name == "wq":
            gain *= cfg.get("q_gain", 1.0)
        return _on_grid(key, shape, bits, gain).astype(dtype)
    raise ValueError(f"no generator for parameter {path} {shape}")


def leaf_key(base, path: str, layer):
    """The key of leaf ``path`` of block ``layer`` (0 outside the blocks),
    from ``base = seed_key(seed)``."""
    return jax.random.fold_in(jax.random.fold_in(base, np.uint32(zlib.crc32(path.encode()))),
                              layer)


def block_shapes(shapes):
    """One block's leaves (``shapes["blocks"]`` without its layer axis)."""
    return jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                                  shapes["blocks"])


def _fill(shapes, prefix: str, cfg: dict, base, layer):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for kp, s in flat:
        path = prefix + _path(kp)
        leaves.append(_leaf(leaf_key(base, path, layer), path, s.shape, s.dtype, cfg))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def block_maker(blk_shapes, cfg: dict):
    """``make(base, layer)``: block ``layer``'s leaves (a tree shaped as
    ``block_shapes(shapes)``) in one jitted call. The key and the layer are
    traced, so one program serves every block and every seed."""
    return jax.jit(lambda base, layer: _fill(blk_shapes, "blocks/", cfg, base, layer))


def rest_maker(shapes, cfg: dict):
    """``make(base)``: every leaf outside the blocks (embedding, final
    norm, head) in one jitted call."""
    rest = {k: v for k, v in shapes.items() if k != "blocks"}
    return jax.jit(lambda base: _fill(rest, "", cfg, base, 0))


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31), seed // 2 ** 31)
