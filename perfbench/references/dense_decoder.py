"""Plain reference of a dense decoder-only transformer: float32, matrix
products at the highest precision, no kernels, no cache, no batching.

It follows the published block: pre-norm (RMSNorm, or LayerNorm with a
bias), grouped-query attention with rotary positions (the first and second
halves of each head rotated as a pair) and an optional sliding window, and
an MLP that is SiLU-gated (``wi`` holds [gate; up]) or squared-ReLU. It
imports nothing of the serving program and reads only the parameter tree
the benchmark made, under the serving program's key names.

The harness drives it a layer at a time: ``embed``, then ``block`` once
per layer with that layer's weights and index, then ``head``. The layer
index picks the window: ``window_pattern``, where the configuration gives
one, is one period of per-layer windows (0: full attention) tiled over the
depth; otherwise every layer takes ``sliding_window``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _norm(p, x, kind: str, eps: float):
    if kind == "rms":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if kind == "layer":
        y = y + p["bias"].astype(jnp.float32)
    return y


def _rope(x, pos, theta: float):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    ang = ang.reshape(pos.shape[0], *([1] * (x.ndim - 2)), half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _w(p):
    return p["w"].astype(jnp.float32)


def window(cfg: dict, layer):
    """Layer ``layer``'s attention window (0: full); ``layer`` may be traced."""
    pattern = cfg.get("window_pattern")
    if pattern:
        return jnp.asarray(pattern, jnp.int32)[layer % len(pattern)]
    return jnp.int32(cfg.get("sliding_window", 0))


def attention(cfg: dict, x, p, pos, layer):
    """The residual stream after the block's attention."""
    t = x.shape[0]
    kv, hd = cfg["n_kv_heads"], cfg["head_dim"]
    g = cfg["n_heads"] // kv
    h = norm(p["norm1"], x, cfg)
    q = _rope((h @ _w(p["attn"]["wq"])).reshape(t, kv, g, hd), pos, cfg["rope_theta"])
    k = _rope((h @ _w(p["attn"]["wk"])).reshape(t, kv, hd), pos, cfg["rope_theta"])
    v = (h @ _w(p["attn"]["wv"])).reshape(t, kv, hd)
    s = jnp.einsum("skgh,tkh->kgst", q, k) * hd ** -0.5
    w = window(cfg, layer)
    ok = (pos[None, :] <= pos[:, None]) & ((w == 0) | (pos[None, :] > pos[:, None] - w))
    s = jnp.where(ok[None, None], s, -jnp.inf)
    o = jnp.einsum("kgst,tkh->skgh", jax.nn.softmax(s, axis=-1), v)
    return x + o.reshape(t, -1) @ _w(p["attn"]["wo"])


def mlp(cfg: dict, p, h):
    """The MLP of ``p`` (``wi``, ``wd``) on normed activations ``h``."""
    h = h @ _w(p["wi"])
    if cfg["act_fn"] == "silu":
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate) * up
    elif cfg["act_fn"] == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(f"reference has no activation {cfg['act_fn']!r}")
    return h @ _w(p["wd"])


def norm(p, x, cfg: dict):
    return _norm(p, x, cfg["norm"], cfg["norm_eps"])


def embed(params, cfg: dict, tokens):
    """(T, d_model) float32 inputs of one sequence ``tokens`` (T,)."""
    return params["embed"]["table"][tokens].astype(jnp.float32)


def block(cfg: dict, x, p, pos, layer):
    """Block ``layer`` (weights ``p``) over one sequence's stream ``x``."""
    with jax.default_matmul_precision("highest"):
        x = attention(cfg, x, p, pos, layer)
        return x + mlp(cfg, p["mlp"], norm(p["norm2"], x, cfg))


def head(params, cfg: dict, x):
    """(T, vocab_size) float32 logits of one sequence's final stream."""
    with jax.default_matmul_precision("highest"):
        x = norm(params["norm_f"], x, cfg)
        return (x @ params["head"]["w"].astype(jnp.float32))[:, : cfg["vocab_size"]]

