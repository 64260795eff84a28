"""The main-path Pallas kernels compile for a TPU v5e at h2o-danube-1.8B widths.

No chip is needed: the TPU compiler installed with JAX compiles for a
described ``v5e:2x2`` topology, and refuses what the chip would refuse
(blocks off the (8, 128) tiling, VMEM over the scoped limit, ops Mosaic does
not lower). Interpret-mode parity tests cannot see any of that. Shapes are
those of one packed serving step: d_model 2560, d_ff 6912, 8 KV heads of
head_dim 80 (GQA 32/8), W4A4 projections plus the W8 ``mlp/wd`` tier, int4
and bf16 paged KV pools at cache_len 2048, and M = 8 (decode slots) or 40
(slots + prefill chunk) token rows.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.h2o_danube_1_8b import CONFIG
from repro.core.outlier import num_outliers
from repro.kernels.lut_gemm import fused_lut_gemm_kernel_call
from repro.kernels.paged_attn import paged_attn_kernel_call
from repro.kernels.topk_outlier import (
    streaming_quantize_outlier_kernel_call,
    topk_outlier_kernel_call,
)

D, F = CONFIG.d_model, CONFIG.d_ff
KV, G, HD = CONFIG.n_kv_heads, CONFIG.n_heads // CONFIG.n_kv_heads, CONFIG.head_dim
SLOTS, CHUNK, CACHE_LEN, BLOCK = 8, 32, 2048, 16
FRAC = 0.005  # the serving spec's outlier budget per side

# (name, K, N, weight bits): every quantized projection of one block
PROJECTIONS = [
    ("attn_q_o", D, KV * G * HD, 4),
    ("attn_k_v", D, KV * HD, 4),
    ("mlp_wi", D, 2 * F, 4),
    ("mlp_wd", F, D, 8),
]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry written for a described chip cannot be read
    # back without one: keep these compiles out of any configured cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not a fallback
    return compiled


@pytest.mark.parametrize("m", [SLOTS, SLOTS + CHUNK])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name,k,n,w_bits", PROJECTIONS)
def test_fused_lut_gemm_compiles(one_chip, name, k, n, w_bits, dtype, m):
    byte_packed = w_bits > 4
    packed_n = n if byte_packed else n // 2

    def step(x, scale, w, bounds, a_book, w_book):
        return fused_lut_gemm_kernel_call(
            x, scale, w, bounds, a_book, w_book, byte_packed=byte_packed,
            mul_form=dtype == jnp.bfloat16, interpret=False)

    _compile(step, one_chip, ((m, k), dtype), ((m, 1), jnp.float32),
             ((k, packed_n), jnp.uint8), ((15,), jnp.float32),
             ((16,), jnp.float32), ((2**w_bits,), jnp.float32))


@pytest.mark.parametrize("m", [SLOTS, SLOTS + CHUNK])
@pytest.mark.parametrize("k_in", [D, F])
def test_orizuru_detect_compiles(one_chip, k_in, m):
    k = num_outliers(k_in, FRAC)
    _compile(lambda x: topk_outlier_kernel_call(x, k, interpret=False),
             one_chip, ((m, k_in), jnp.float32))


@pytest.mark.parametrize("mul_form", [False, True])
@pytest.mark.parametrize("k_in", [D, F])
def test_orizuru_streaming_compiles(one_chip, k_in, mul_form):
    k = num_outliers(k_in, FRAC)

    def step(x, scale, bounds):
        return streaming_quantize_outlier_kernel_call(
            x, scale, bounds, k, mul_form=mul_form, interpret=False)

    m = SLOTS + CHUNK
    _compile(step, one_chip, ((m, k_in), jnp.float32), ((m, 1), jnp.float32),
             ((15,), jnp.float32))


@pytest.mark.parametrize("window", [0, CONFIG.sliding_window])
@pytest.mark.parametrize("pool", ["bf16", "int4"])
def test_paged_attn_compiles(one_chip, pool, window):
    rows = SLOTS + CHUNK  # the packed step: one token per row
    max_blk = CACHE_LEN // BLOCK
    n_blocks = SLOTS * max_blk
    if pool == "bf16":
        storage = [((n_blocks, BLOCK, KV, HD), jnp.bfloat16)] * 2
    else:
        idx = ((n_blocks, BLOCK, KV, HD // 2), jnp.uint8)
        scale = ((n_blocks, BLOCK, KV, 1), jnp.float32)
        storage = [idx, scale, idx, scale, ((16,), jnp.float32)]

    def step(q, bt, ctx, q_pos, *pools):
        return paged_attn_kernel_call(
            q, *pools, block_tables=bt, ctx_lens=ctx, q_pos=q_pos,
            window=window, interpret=False)

    _compile(step, one_chip, ((rows, 1, KV, G, HD), jnp.bfloat16),
             ((rows, max_blk), jnp.int32), ((rows,), jnp.int32),
             ((rows, 1), jnp.int32), *storage)
