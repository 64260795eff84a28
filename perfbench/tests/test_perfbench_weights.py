"""Seeded weights made a block at a time, the per-layer build of the served
tree and the layer-streamed reference, on the CPU at small sizes; and, by
shapes alone, the size of each weight-making call at a mixture-of-experts
configuration that one chip holds only quantized."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import REPO, TINY, stacked_weights, whole_logits

from perfbench import discover, harness, weights
from repro.configs.base import ModelConfig, get_smoke_config
from repro.core import dequantize_weight, quantize_weight
from repro.models.model import build, quantize_model, quantize_params

H2O = json.loads((REPO / "perfbench" / "configs" / "h2o_danube_1_8b.json").read_text())


def config(size: dict, **over) -> dict:
    cfg = {**H2O, **size, **over}
    if cfg["sliding_window"]:
        cfg["sliding_window"] = 48
    return cfg


def shapes_of(cfg: dict):
    return jax.eval_shape(build(discover.model_config(cfg)).init, jax.random.PRNGKey(0))


def smoke_moe(**over) -> dict:
    """qwen2-moe's smoke configuration of the program, as a configuration file."""
    mc = get_smoke_config("qwen2_moe_a2_7b")
    return {**H2O, **{f.name: getattr(mc, f.name) for f in dataclasses.fields(mc)},
            "reference": "moe_decoder", **over}


def as_projections(shapes):
    """``shapes`` with each layer's raw (E, k, n) expert stacks held as
    ``{"w": ...}`` projections, the form the quantizer takes."""
    moe = dict(shapes["blocks"]["moe"])
    for name in weights.EXPERT_STACKS:
        moe[name] = {"w": moe[name]}
    return dict(shapes, blocks=dict(shapes["blocks"], moe=moe))


@pytest.mark.parametrize("form", ["raw", "dict"])
def test_moe_tree_is_generated_and_its_experts_on_the_grid(form):
    cfg = smoke_moe(quant=dict(H2O["quant"], rules=[]))
    shapes = shapes_of(cfg)
    if form == "dict":
        shapes = as_projections(shapes)
    params = stacked_weights(shapes, cfg, 2 ** 33 + 5)
    moe = params["blocks"]["moe"]
    assert moe["router"]["w"].dtype == jnp.float32
    d = cfg["d_model"]
    assert abs(float(jnp.std(moe["router"]["w"])) * d ** 0.5 - 1.0) < 0.1
    for name in weights.EXPERT_STACKS:
        stack = moe[name] if form == "raw" else moe[name]["w"]
        n_layers, n_exp = stack.shape[:2]
        assert n_exp == cfg["n_experts"]
        for i in range(n_layers):
            for e in range(n_exp):
                w = stack[i, e].astype(jnp.float32)
                back = dequantize_weight(quantize_weight(w, nbits=cfg["quant"]["w_bits"]))
                err = jnp.abs(back - w) / jnp.max(jnp.abs(w), axis=0)
                assert float(err.max()) <= 2.0 ** -8, (name, i, e)
    if form == "dict":  # the program's quantizer takes them, one codebook per expert
        q = quantize_params(params["blocks"], discover.quant_spec(cfg["quant"]), path="blocks")
        qw = q["moe"]["wd"].qw
        back = jax.vmap(jax.vmap(dequantize_weight))(qw)
        w = moe["wd"]["w"].astype(jnp.float32)
        assert float((jnp.abs(back - w) / jnp.max(jnp.abs(w), axis=-2, keepdims=True)).max()) \
            <= 2.0 ** -8


def test_expert_rules_resolve_on_expert_paths():
    quant = dict(H2O["quant"], rules=[["moe/wd", {"w_bits": 8}]])
    assert discover.resolve_bits(quant, "blocks/moe/wd") == 8
    assert discover.resolve_bits(quant, "blocks/moe/wi") == 4
    assert discover.resolve_bits(quant, "blocks/moe/shared/wd") == 4
    cfg = smoke_moe(quant=quant)
    params = stacked_weights(shapes_of(cfg), cfg, 3)
    for name, bits in (("wd", 8), ("wi", 4)):
        w = np.asarray(params["blocks"]["moe"][name], np.float64)
        top = 2 ** bits - 1
        levels = w / np.abs(w).max(axis=-2, keepdims=True) * top  # odd integers in [-top, top]
        assert np.abs(levels - np.rint(levels)).max() < 1e-3
        assert len(np.unique(np.rint(levels))) > 2 ** (bits - 1)
        assert np.abs(levels).max(axis=-2).min() == pytest.approx(top)  # both ends in every channel


def test_blocks_are_deterministic_and_free_of_depth():
    shallow, deep = config(TINY), config(TINY, n_layers=5)
    blk = weights.block_shapes(shapes_of(shallow))
    base = weights.seed_key(2 ** 32 + 7)
    a = weights.block_maker(blk, shallow)(base, 1)
    again = weights.block_maker(blk, shallow)(weights.seed_key(2 ** 32 + 7), 1)
    b = weights.block_maker(blk, deep)(base, 1)
    other = weights.block_maker(blk, shallow)(base, 0)
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    for (kp, x), y, z, o in zip(flat_a, jax.tree_util.tree_leaves(again),
                                jax.tree_util.tree_leaves(b), jax.tree_util.tree_leaves(other)):
        path = jax.tree_util.keystr(kp)
        assert bool((x == y).all()), path
        assert not bool((x == o).all()), path
        if "'wo'" in path or "'wd'" in path:
            # the output projections' gain is 1/sqrt(2 * layers); the draw is the same
            ratio = np.asarray(z, np.float64) / np.asarray(x, np.float64)
            assert np.allclose(ratio, (2 / 5) ** 0.5, rtol=2 ** -7), path
        else:
            assert bool((x == z).all()), path
    # the whole tree is the same blocks, stacked
    whole = stacked_weights(shapes_of(deep), deep, 2 ** 32 + 7)
    for x, y in zip(jax.tree_util.tree_leaves(b), jax.tree_util.tree_leaves(whole["blocks"])):
        assert bool((x == y[1]).all())


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_per_layer_build_matches_quantize_model(kind, monkeypatch):
    cfg = config(TINY, n_layers=3) if kind == "dense" else smoke_moe(n_layers=3)
    model = build(discover.model_config(cfg))
    spec = discover.quant_spec(cfg["quant"])
    whole = stacked_weights(shapes_of(cfg), cfg, 11)
    fill, made = weights._fill, []

    def one_block_at_most(shapes, prefix, *args):
        made.append((prefix, jax.tree_util.tree_leaves(shapes)))
        return fill(shapes, prefix, *args)

    monkeypatch.setattr(weights, "_fill", one_block_at_most)
    built = harness.build_params(model, cfg, spec, 11)
    # the weight-making calls made one block, or the leaves outside the blocks
    blk = jax.tree_util.tree_leaves(weights.block_shapes(shapes_of(cfg)))
    assert sorted(p for p, _ in made) == ["", "blocks/"]
    assert [s.shape for s in dict(made)["blocks/"]] == [s.shape for s in blk]
    # quantize_model traced as the build traces its quantizer: eagerly, XLA
    # rounds the program's structural activation codebook differently
    want = jax.jit(lambda p: quantize_model(model, p, spec))(whole)
    assert jax.tree_util.tree_structure(built) == jax.tree_util.tree_structure(want)
    for (kp, x), y in zip(jax.tree_util.tree_flatten_with_path(built)[0],
                          jax.tree_util.tree_leaves(want)):
        assert x.dtype == y.dtype and x.shape == y.shape, jax.tree_util.keystr(kp)
        assert bool((x == y).all()), jax.tree_util.keystr(kp)


@pytest.mark.parametrize("kind", ["dense", "dense_windows", "moe"])
def test_layer_streamed_reference_matches_whole_tree(kind):
    if kind == "moe":
        cfg = smoke_moe()
    else:
        cfg = config(TINY, n_layers=4)
        if kind == "dense_windows":
            cfg["window_pattern"] = [8, 8, 0]
    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, cfg["vocab_size"], size=n).tolist() for n in (20, 33, 7)]
    starts = [10, 5, 3]
    got = harness.reference_gaps(cfg, 5, seqs, starts, REPO)
    ref = discover.reference(cfg["reference"], REPO / "perfbench")
    params = stacked_weights(shapes_of(cfg), cfg, 5)
    want = []
    for s, st in zip(seqs, starts):
        lg = whole_logits(ref, params, cfg, jnp.asarray(s))[st:-1]
        chosen = jnp.take_along_axis(lg, jnp.asarray(s[st + 1:])[:, None], axis=1)[:, 0]
        want.append(np.asarray((lg.max(-1) - chosen) / lg.std(-1)))
    np.testing.assert_allclose(got["all"], np.concatenate(want), rtol=1e-6, atol=1e-6)
    if kind == "dense_windows":  # the windows matter at these lengths
        cfg_full = dict(cfg, window_pattern=[0])
        full = harness.reference_gaps(cfg_full, 5, seqs, starts, REPO)
        assert not np.allclose(full["all"], got["all"], atol=1e-4)


def test_weight_calls_at_published_moe_widths_hold_one_layer():
    """A model whose bf16 tree one v5e cannot hold (28 layers of 64 experts
    of 896 at d 2304, vocabulary 98304): the harness's weight-making calls
    each output one block, or the embedding and head, under 1 GB."""
    cfg = dict(H2O, family="moe", n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4,
               head_dim=128, d_ff=896, n_experts=64, experts_per_token=8, vocab_size=98304,
               sliding_window=0)
    mc = discover.model_config(cfg)
    assert isinstance(mc, ModelConfig) and mc.n_experts == 64
    shapes = shapes_of(cfg)
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(t))
    assert nbytes(shapes) > 16e9
    base = weights.seed_key(1)
    block = jax.eval_shape(weights.block_maker(weights.block_shapes(shapes), cfg), base, 3)
    rest = jax.eval_shape(weights.rest_maker(shapes, cfg), base)
    assert nbytes(block) < 1e9 and nbytes(rest) < 1e9
    assert nbytes(block) * cfg["n_layers"] + nbytes(rest) == nbytes(shapes)


def _earlier_dense_logits(params, cfg: dict, tokens):
    """The dense reference as it stood before it was split into ``embed``,
    ``block`` and ``head`` (a whole stacked tree in one scan, one window for
    every layer), kept here to pin the split against it."""
    def norm(p, x):
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg["norm_eps"])
        return y * p["scale"].astype(jnp.float32)

    def rope(x, pos):
        half = x.shape[-1] // 2
        freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = (pos.astype(jnp.float32)[:, None] * freq).reshape(
            pos.shape[0], *([1] * (x.ndim - 2)), half)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)

    def w(p):
        return p["w"].astype(jnp.float32)

    def blk(x, p, pos):
        t, kv, hd = x.shape[0], cfg["n_kv_heads"], cfg["head_dim"]
        g = cfg["n_heads"] // kv
        h = norm(p["norm1"], x)
        q = rope((h @ w(p["attn"]["wq"])).reshape(t, kv, g, hd), pos)
        k = rope((h @ w(p["attn"]["wk"])).reshape(t, kv, hd), pos)
        v = (h @ w(p["attn"]["wv"])).reshape(t, kv, hd)
        s = jnp.einsum("skgh,tkh->kgst", q, k) * hd ** -0.5
        ok = pos[None, :] <= pos[:, None]
        if cfg.get("sliding_window", 0):
            ok &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
        s = jnp.where(ok[None, None], s, -jnp.inf)
        o = jnp.einsum("kgst,tkh->skgh", jax.nn.softmax(s, axis=-1), v)
        x = x + o.reshape(t, -1) @ w(p["attn"]["wo"])
        gate, up = jnp.split(norm(p["norm2"], x) @ w(p["mlp"]["wi"]), 2, axis=-1)
        return x + (jax.nn.silu(gate) * up) @ w(p["mlp"]["wd"])

    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(lambda c, p: (blk(c, p, pos), None), x, params["blocks"])
        x = norm(params["norm_f"], x)
        return (x @ params["head"]["w"].astype(jnp.float32))[:, : cfg["vocab_size"]]


def test_layer_streamed_reference_keeps_the_earlier_dense_form():
    """The harness's layer loop over the split reference gives the gaps of
    the dense reference's earlier whole-tree form, with sequences longer
    than the sliding window so that the window bites."""
    cfg = config(TINY, n_layers=3)
    assert cfg["norm"] == "rms" and cfg["act_fn"] == "silu" and cfg["sliding_window"] == 48
    rng = np.random.RandomState(1)
    seqs = [rng.randint(0, cfg["vocab_size"], size=n).tolist() for n in (90, 61, 12)]
    starts = [40, 50, 4]
    got = harness.reference_gaps(cfg, 2 ** 31 + 9, seqs, starts, REPO)
    params = stacked_weights(shapes_of(cfg), cfg, 2 ** 31 + 9)
    want = []
    for s, st in zip(seqs, starts):
        lg = _earlier_dense_logits(params, cfg, jnp.asarray(s))[st:-1]
        chosen = jnp.take_along_axis(lg, jnp.asarray(s[st + 1:])[:, None], axis=1)[:, 0]
        want.append(np.asarray((lg.max(-1) - chosen) / lg.std(-1)))
    np.testing.assert_allclose(got["all"], np.concatenate(want), rtol=1e-6, atol=1e-6)
