"""Dense decoder-only transformer LM.

Covers llama3.2-1b, h2o-danube (SWA), command-r-plus (parallel blocks,
LayerNorm, no bias), nemotron-4 (squared-ReLU, LayerNorm) and the musicgen
backbone (sinusoidal positions, EnCodec-token vocab). Layer stack runs under
``lax.scan`` over stacked params so HLO size is depth-independent; optional
per-block remat.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L

__all__ = ["init", "apply", "init_caches", "cache_policies"]


def _init_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "norm1": L.norm_init(cfg.d_model, cfg.norm, dtype),
        "attn": L.attention_init(k1, cfg, dtype),
        "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.act_fn, dtype),
    }
    if not cfg.parallel_blocks:
        p["norm2"] = L.norm_init(cfg.d_model, cfg.norm, dtype)
    return p


def init(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_blocks, cfg.n_layers)
    if cfg.scan_layers:
        blocks = jax.vmap(lambda k: _init_block(k, cfg, dtype))(keys)
    else:
        blocks = [_init_block(k, cfg, dtype) for k in keys]
    params = {
        "embed": L.embed_init(k_emb, cfg.vocab_padded, cfg.d_model, dtype),
        "blocks": blocks,
        "norm_f": L.norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab_padded, dtype)
    return params


def init_caches(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16,
                quantized: bool = False, layout: str = "ring",
                block_size: int = 16, n_blocks: int = 0):
    """Stacked (L, ...) KV caches.

    layout="ring" (default): dense ring buffer per request slot; cache_len
    should be the window for SWA archs (bounded memory at 500k) and max_seq
    otherwise. quantized=True -> K-Means int4 KV storage (see
    layers.init_kv_cache).

    layout="paged": a global pool of ``n_blocks`` blocks of ``block_size``
    tokens per layer (layers.init_paged_kv_cache); ``batch``/``cache_len``
    only size the default pool (``batch * ceil(cache_len / block_size)``
    blocks when n_blocks=0). The returned tree holds pools ONLY — the
    serving scheduler attaches per-call ``block_tables``/``ctx_lens``
    (repro.serving.paged_cache.attach_tables) before model.apply. SWA
    configs use the same pool with LOGICAL (unclamped) tables: position p
    always lives at table[p // block_size], and the scheduler frees table
    entries that fall wholly out of the window (windowed_paged policy) —
    only the ring layout clamps cache_len to the window.
    """
    if layout == "paged":
        if n_blocks <= 0:
            n_blocks = batch * -(-cache_len // block_size)
        one = lambda: L.init_paged_kv_cache(cfg, n_blocks, block_size, dtype, quantized)
    else:
        if cfg.sliding_window:
            cache_len = min(cache_len, cfg.sliding_window)
        one = lambda: L.init_kv_cache(cfg, batch, cache_len, dtype, quantized)
    if cfg.scan_layers:
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[one() for _ in range(cfg.n_layers)])
    return [one() for _ in range(cfg.n_layers)]


def cache_policies(cfg: ModelConfig):
    """Per-layer cache policy for the serving scheduler: every dense block is
    paged KV; SWA configs get the windowed variant (out-of-window blocks are
    freed, capping steady-state blocks at ceil(window / block_size) + 1)."""
    from repro.serving.paged_cache import CachePolicy

    if cfg.sliding_window:
        pol = CachePolicy("windowed_paged", window=cfg.sliding_window)
    else:
        pol = CachePolicy("paged_kv")
    return [pol] * cfg.n_layers


def _block_apply(p, x, cfg: ModelConfig, positions, cache):
    window = cfg.sliding_window
    if cfg.parallel_blocks:
        n = L.norm_apply(p["norm1"], x, cfg.norm)
        a, new_cache = L.attention_apply(
            p["attn"], n, cfg, positions=positions, cache=cache, window=window
        )
        m = L.mlp_apply(p["mlp"], n, cfg.act_fn)
        x = x + a + m
    else:
        a, new_cache = L.attention_apply(
            p["attn"], L.norm_apply(p["norm1"], x, cfg.norm), cfg,
            positions=positions, cache=cache, window=window,
        )
        x = x + a
        x = x + L.mlp_apply(p["mlp"], L.norm_apply(p["norm2"], x, cfg.norm), cfg.act_fn)
    return constrain(x, "batch", "seq_sp", "d_model"), new_cache


def _embed_in(params, cfg: ModelConfig, tokens, positions):
    with jax.named_scope("embed"):
        x = params["embed"]["table"][tokens].astype(jnp.dtype(cfg.compute_dtype))
        if cfg.pos_embed == "sinusoidal":
            pe = L.sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
            x = x + (pe if positions.ndim == 2 else pe[None])  # (B,S,d) | (1,S,d)
        return constrain(x, "batch", "seq_sp", "d_model")


def _logits_out(params, cfg: ModelConfig, x):
    with jax.named_scope("logits"):
        x = L.norm_apply(params["norm_f"], x, cfg.norm)
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["table"].astype(x.dtype).T
        else:
            logits = L.dense_apply(params["head"], x)
        return constrain(logits.astype(jnp.float32), "batch", "seq", "vocab")


def apply(params, cfg: ModelConfig, tokens: jax.Array, *, positions=None, caches=None, last_only: bool = False, return_hidden_only: bool = False):
    """Forward pass. tokens: (B, S) int32.

    positions: (S,) absolute positions shared across the batch (defaults to
    arange — training/prefill), or (B, S) per-row (continuous-batching:
    ring decode at per-request depths, and the serving scheduler's packed
    token-budget step, where each row is ONE token of some request and
    position -1 marks an unused row). caches: stacked KV caches for
    decode/prefill, returned updated; paged caches may carry per-call
    ``block_tables``/``ctx_lens``/``token_slots`` (see
    repro.serving.paged_cache.attach_tables).
    Returns (logits f32 (B, S, vocab_padded), new_caches).
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    x = _embed_in(params, cfg, tokens, positions)

    if cfg.scan_layers:
        def body(carry, xs):
            if caches is None:
                p = xs
                y, _ = _block_apply(p, carry, cfg, positions, None)
                return y, None
            p, c = xs
            y, nc = _block_apply(p, carry, cfg, positions, c)
            return y, nc

        if cfg.remat in ("block", "double"):
            body = jax.checkpoint(body)
        if cfg.remat == "double" and caches is None:
            # sqrt(L) checkpointing: nested checkpointed scans -> only O(sqrt L)
            # residual-stream carries live at once instead of O(L). This is
            # what brings the 104B train cell under HBM (EXPERIMENTS §Perf).
            l = cfg.n_layers
            g1 = max(d for d in range(1, int(l**0.5) + 1) if l % d == 0)

            @jax.checkpoint
            def group_body(carry, xs_group):
                y, _ = jax.lax.scan(body, carry, xs_group)
                return y, None

            grouped = jax.tree.map(
                lambda a: a.reshape(g1, l // g1, *a.shape[1:]), params["blocks"]
            )
            x, _ = jax.lax.scan(group_body, x, grouped)
            new_caches = None
        else:
            xs = params["blocks"] if caches is None else (params["blocks"], caches)
            # the scan's own work (each layer's weights and pools sliced in,
            # pools written back) is named apart from the layers' scopes
            with jax.named_scope("layer_scan"):
                x, new_caches = jax.lax.scan(body, x, xs)
    else:
        new_caches = []
        for i, p in enumerate(params["blocks"]):
            c = None if caches is None else caches[i]
            x, nc = _block_apply(p, x, cfg, positions, c)
            new_caches.append(nc)
        if caches is None:
            new_caches = None

    if last_only:
        x = x[:, -1:]
    if return_hidden_only:
        from repro.models.layers import norm_apply
        return norm_apply(params["norm_f"], x, cfg.norm), new_caches
    return _logits_out(params, cfg, x), new_caches
