"""Plain reference of a mixture-of-experts decoder-only transformer:
float32, matrix products at the highest precision, no kernels, no cache,
no batching, no capacity: every token reaches all of its experts.

Attention, norms, embedding and head are the dense reference's
(``dense_decoder``). The MLP is routed: a float32 router, softmax over the
experts, the top ``experts_per_token`` (their weights renormalised to sum
to one unless ``norm_topk_prob`` is false), each expert a SiLU-gated or
squared-ReLU MLP; plus, where the configuration has shared experts, one
wide shared MLP under a sigmoid gate. Expert weights are read as raw
``(E, k, n)`` stacks or as ``{"w": (E, k, n)}``, under the serving
program's key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.references import dense_decoder as dense
from perfbench.references.dense_decoder import embed, head  # noqa: F401


def _stack(p):
    return (p["w"] if isinstance(p, dict) else p).astype(jnp.float32)


def _act(cfg: dict, u):
    if cfg["act_fn"] == "silu":
        gate, up = jnp.split(u, 2, axis=-1)
        return jax.nn.silu(gate) * up
    if cfg["act_fn"] == "relu2":
        return jnp.square(jax.nn.relu(u))
    raise ValueError(f"reference has no activation {cfg['act_fn']!r}")


def moe(cfg: dict, p, h):
    """The routed MLP (and shared expert) of ``p`` on normed ``h`` (T, d)."""
    gates = jax.nn.softmax(h @ p["router"]["w"].astype(jnp.float32), axis=-1)
    topw, topi = jax.lax.top_k(gates, cfg["experts_per_token"])
    if cfg.get("norm_topk_prob", True):
        topw = topw / topw.sum(-1, keepdims=True)
    wi, wd = _stack(p["wi"]), _stack(p["wd"])
    # each (token, expert) pair once, grouped by expert: every token reaches
    # every expert it chose, and no expert it did not
    k = topi.shape[1]
    order = jnp.argsort(topi.reshape(-1), stable=True)
    tok = order // k
    sizes = jnp.zeros((wi.shape[0],), jnp.int32).at[topi.reshape(-1)].add(1)
    hi = jax.lax.Precision.HIGHEST
    u = _act(cfg, jax.lax.ragged_dot(h[tok], wi, sizes, precision=hi))
    y = jax.lax.ragged_dot(u, wd, sizes, precision=hi)
    out = jnp.zeros_like(h).at[tok].add(topw.reshape(-1)[order][:, None] * y)
    if cfg.get("n_shared_experts"):
        sg = jax.nn.sigmoid(h @ p["shared_gate"]["w"].astype(jnp.float32))
        out = out + sg * dense.mlp(cfg, p["shared"], h)
    return out


def block(cfg: dict, x, p, pos, layer):
    """Block ``layer`` (weights ``p``) over one sequence's stream ``x``."""
    with jax.default_matmul_precision("highest"):
        x = dense.attention(cfg, x, p, pos, layer)
        return x + moe(cfg, p["moe"], dense.norm(p["norm2"], x, cfg))

