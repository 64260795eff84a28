"""Seeded weights for a served configuration, made on the device in one
jitted call, in the dtype they are served in.

Every projection that the configuration quantizes is drawn on the grid its
K-Means quantizer can represent: per output channel a scale, times one of
``2**bits`` evenly spaced levels in [-1, 1], each level equally likely. The
quantizer's quantile initialisation then lands on the levels and Lloyd's
iterations keep them, so weight quantization is lossless up to bf16
rounding. The correctness check compares the served tokens with a float32
reference of these same bf16 weights: what it measures is the serving
path (activation quantization, Orizuru compensation, the int4 paged KV
cache, every kernel), not weight rounding that no later change can remove.
A control one bit lower cannot represent the grid and shows.

Output projections (``wo``, ``wd``) are scaled by ``1/sqrt(2 * layers)`` so
that the residual stream stays dominated by no single block; random weights
without it give a stack whose logits swing with one-ulp input changes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.discover import QUANT_LEAVES, resolve_bits


def _path(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in keypath)


def grid_levels(bits: int) -> tuple[float, float]:
    """(spacing, rms) of ``2**bits`` evenly spaced levels on [-1, 1]."""
    n = 2 ** bits
    return 2.0 / (n - 1), math.sqrt((n + 1) / (3.0 * (n - 1)))


def _leaf(key, path: str, shape, dtype, cfg: dict):
    leaf = path.rsplit("/", 2)
    d = cfg["d_model"]
    if path.endswith("embed/table"):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)
    if path.endswith("head/w"):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(d)).astype(dtype)
    if leaf[-1] == "scale" and leaf[-2].startswith("norm"):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if leaf[-1] == "bias" and leaf[-2].startswith("norm"):
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if leaf[-1] == "w" and leaf[-2] in QUANT_LEAVES:
        bits = resolve_bits(cfg["quant"], path.rsplit("/", 1)[0])
        k_in = shape[-2]
        kb, ks = jax.random.split(key)
        raw = jax.random.bits(kb, shape, jnp.uint8)
        idx = (raw & (2 ** bits - 1)).astype(jnp.float32)
        _, rms = grid_levels(bits)
        gain = 1.0 / (math.sqrt(k_in) * rms)
        if leaf[-2] in ("wo", "wd"):
            gain /= math.sqrt(2 * cfg["n_layers"])
        if leaf[-2] == "wq":
            gain *= cfg.get("q_gain", 1.0)
        col = gain * jnp.exp(0.25 * jax.random.normal(
            ks, shape[:-2] + (1, shape[-1]), jnp.float32))
        level = (2.0 * idx - (2 ** bits - 1)) / (2 ** bits - 1)
        return (level * col).astype(dtype)
    raise ValueError(f"no generator for parameter {path} {shape}")


def make_weights(shapes, cfg: dict, seed: int):
    """The parameter tree of ``shapes`` (a tree of ShapeDtypeStruct, as
    ``jax.eval_shape(model.init, key)`` gives it), filled from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(_path(kp), s.shape, s.dtype) for kp, s in flat]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(specs))
        return [_leaf(k, p, sh, dt, cfg) for k, (p, sh, dt) in zip(keys, specs)]

    leaves = build(_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _key(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31), seed // 2 ** 31)

