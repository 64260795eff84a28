"""Plain reference of a dense decoder-only transformer: float32, matrix
products at the highest precision, no kernels, no cache, no batching.

It follows the published block: pre-norm (RMSNorm, or LayerNorm with a
bias), grouped-query attention with rotary positions (the first and second
halves of each head rotated as a pair) and an optional sliding window, and
an MLP that is SiLU-gated (``wi`` holds [gate; up]) or squared-ReLU. It
imports nothing of the serving program and reads only the parameter tree
the benchmark made, under the serving program's key names.
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


def _block(cfg: dict, x, p, pos):
    t = x.shape[0]
    kv, hd = cfg["n_kv_heads"], cfg["head_dim"]
    g = cfg["n_heads"] // kv
    eps, kind = cfg["norm_eps"], cfg["norm"]
    h = _norm(p["norm1"], x, kind, eps)
    q = _rope((h @ _w(p["attn"]["wq"])).reshape(t, kv, g, hd), pos, cfg["rope_theta"])
    k = _rope((h @ _w(p["attn"]["wk"])).reshape(t, kv, hd), pos, cfg["rope_theta"])
    v = (h @ _w(p["attn"]["wv"])).reshape(t, kv, hd)
    s = jnp.einsum("skgh,tkh->kgst", q, k) * hd ** -0.5
    ok = pos[None, :] <= pos[:, None]
    if cfg.get("sliding_window", 0):
        ok &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    s = jnp.where(ok[None, None], s, -jnp.inf)
    o = jnp.einsum("kgst,tkh->skgh", jax.nn.softmax(s, axis=-1), v)
    x = x + o.reshape(t, -1) @ _w(p["attn"]["wo"])
    h = _norm(p["norm2"], x, kind, eps) @ _w(p["mlp"]["wi"])
    if cfg["act_fn"] == "silu":
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate) * up
    elif cfg["act_fn"] == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(f"reference has no activation {cfg['act_fn']!r}")
    return x + h @ _w(p["mlp"]["wd"])


def logits(params, cfg: dict, tokens):
    """(T, vocab_size) float32 logits of one sequence ``tokens`` (T,)."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(lambda c, p: (_block(cfg, c, p, pos), None), x,
                            params["blocks"])
        x = _norm(params["norm_f"], x, cfg["norm"], cfg["norm_eps"])
        return (x @ params["head"]["w"].astype(jnp.float32))[:, : cfg["vocab_size"]]
