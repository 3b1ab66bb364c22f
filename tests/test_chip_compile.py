"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs any block shape; the TPU compiler (Mosaic) refuses
blocks that break the (8, 128) tiling rule, primitives it cannot lower
and working sets beyond the scoped VMEM limit.  These tests compile each
kernel family at real widths against a ``v5e:2x2`` topology description
(no chip needed), so such a refusal fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist workers all
import every test module.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tuner import SCAN_TILES
from repro.kernels.dispatch import force_pallas
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import TileConfig, gemm
from repro.kernels.grouped_gemm import grouped_gemm, ragged_gemm
from repro.kernels.mamba_scan.ops import ssd_scan

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` on Pallas (not interpret) for the described chip and
    compile it; returns the compiled text, which must hold the kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with force_pallas(True):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


# stablelm-3b decode GEMMs: batch 8/16 rows against the fused QKV width
# (3 x 2560) with K = d_model, plus the FFN down-projection (K = d_ff).
GEMM_CASES = [
    (8, 7680, 2560, TileConfig(8, 512, 512)),
    (16, 7680, 2560, TileConfig(16, 256, 256)),
    (8, 7680, 2560, TileConfig(8, 512, 256, split_k=4)),
    (16, 2560, 6912, TileConfig(16, 512, 512, split_k=8)),
    (8, 7680, 2560, TileConfig(8, 512, 512, stream_k=8)),
    (16, 7680, 2560, TileConfig(16, 256, 256, stream_k=5)),
    (16, 2560, 6912, TileConfig(16, 128, 512, stream_k=3)),
]


@pytest.mark.parametrize("M,N,K,tile", GEMM_CASES,
                         ids=[f"{m}x{n}x{k}-{t.key()}"
                              for m, n, k, t in GEMM_CASES])
def test_gemm_decode_shapes_compile(one_chip, M, N, K, tile):
    _compile(lambda a, b: gemm(a, b, tile=tile, interpret=False),
             one_chip, ((M, K), BF16), ((K, N), BF16))


# The search space's largest working sets: the biggest plain tile in f32
# and the deepest split-K reduce block, both with transposed operands.
@pytest.mark.parametrize("tile,dtype", [
    (TileConfig(512, 512, 512), jnp.float32),
    (TileConfig(512, 512, 512, split_k=8), BF16),
    (TileConfig(512, 512, 512, stream_k=8), BF16),
], ids=["512cube-f32", "512cube-s8", "512cube-g8"])
def test_gemm_largest_tiles_compile(one_chip, tile, dtype):
    M = N = 1024
    K = 4096
    _compile(lambda a, b: gemm(a, b, ta=True, tb=True, tile=tile,
                               interpret=False),
             one_chip, ((K, M), dtype), ((N, K), dtype))


@pytest.mark.parametrize("bm", [8, 16])
def test_grouped_gemm_compiles(one_chip, bm):
    tile = TileConfig(bm, 512, 512)
    _compile(lambda a, b: grouped_gemm(a, b, tile=tile, interpret=False),
             one_chip, ((3, 8, 2560), BF16), ((3, 2560, 6912), BF16))


def test_ragged_gemm_compiles(one_chip):
    tile = TileConfig(16, 256, 512)
    sizes = jnp.asarray([16, 32, 16], jnp.int32)
    _compile(lambda a, b: ragged_gemm(a, b, sizes, tile=tile,
                                      interpret=False),
             one_chip, ((64, 2560), BF16), ((3, 2560, 6912), BF16))


@pytest.mark.parametrize("hd,heads,kv_heads", [(80, 32, 32), (128, 40, 8), (224, 32, 32)],
                         ids=["stablelm-hd80", "qwen3-hd128", "zamba2-7b-hd224"])
def test_flash_prefill_compiles(one_chip, hd, heads, kv_heads):
    T, S = 128, 256
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False),
             one_chip, ((4, heads, T, hd), BF16),
             ((4, kv_heads, S, hd), BF16), ((4, kv_heads, S, hd), BF16))


def test_flash_decode_compiles(one_chip):
    S = 1024
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             q_offset=S - 8, bq=8,
                                             interpret=False),
             one_chip, ((8, 32, 8, 80), BF16), ((8, 32, S, 80), BF16),
             ((8, 32, S, 80), BF16))


# The decode kernel against a stacked, positions-minor cache at the cells'
# widths: stablelm-3b (MHA, hd 80) and one chip's share of qwen3-14b
# (GQA 5:1, hd 128), cache capacity 769 rounded up to 896; and at the long
# capacities the repo declares, where the positions take several grid
# steps: stablelm-3b at 8k, qwen3-14b at decode_32k, and zamba2-1.2b's
# shared attention (hd 64) at long_500k; and zamba2-7b's shared blocks (MHA
# of 32 heads of 224, the KV stack of its 4 uses).
@pytest.mark.parametrize("L,B,hq,hkv,hd,S", [
    (32, 16, 32, 32, 80, 896), (40, 32, 10, 2, 128, 896), (8, 4, 16, 8, 128, 896),
    (2, 16, 32, 32, 80, 8192), (1, 8, 40, 8, 128, 32768),
    (1, 1, 32, 32, 64, 524288), (4, 32, 32, 32, 224, 896)],
    ids=["stablelm-hd80", "qwen3-tp4-hd128", "gqa-2to1", "stablelm-8k",
         "qwen3-32k", "zamba2-512k", "zamba2-7b-hd224"])
def test_decode_attention_compiles(one_chip, L, B, hq, hkv, hd, S):
    cache = ((L, B, hkv, hd, S), BF16)
    new = ((B, hkv * hd), BF16)
    _compile(lambda q, kn, vn, k, v, i, n, w: decode_attention(
                 q, kn, vn, k, v, i, n, w, interpret=False),
             one_chip, ((B, hq * hd), jnp.float32), new, new, cache, cache,
             ((), jnp.int32), ((), jnp.int32), ((), jnp.int32))


def test_decode_step_updates_the_cache_in_place(one_chip):
    """stablelm-3b's decode step at its widths (two layers), compiled as
    `greedy_decode` compiles it: the cache is donated and aliased to the
    output, and the program holds no buffer for it of its own — no layer
    of the cache is sliced out, relaid out or written back whole."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding

    from repro.configs import get_arch
    from repro.dist.sharding import named, params_pspecs
    from repro.models import build_model
    from repro.train.serve_loop import serving_jit

    mesh = Mesh(np.asarray(list(one_chip.device_set)).reshape(1, 1), ("data", "model"))
    model = build_model(dataclasses.replace(get_arch("stablelm-3b"), n_layers=2),
                        mesh=mesh)

    def sds(tree, shardings):
        return jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sh), tree, shardings)

    params = jax.eval_shape(lambda k: model.init(k, BF16), jax.random.PRNGKey(0))
    params = sds(params, named(mesh, params_pspecs(model, mesh)))
    cache = jax.eval_shape(lambda: model.init_cache(batch=16, s_max=769, dtype=BF16))
    cache = sds(cache, named(mesh, model.cache_pspecs(mesh, cache)))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    with force_pallas(True):
        compiled = serving_jit(model.decode_step).lower(
            params, jax.ShapeDtypeStruct((16, 1), jnp.int32, sharding=rep), cache,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    layer_bytes = cache_bytes // (2 * 2)
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < layer_bytes // 8
    assert "goldyloc_decode_attn" in compiled.as_text()


def test_zamba2_decode_step_updates_its_caches_in_place(one_chip):
    """zamba2-7b's decode step at its widths (12 layers, the uses at 6 and
    11, one of each shared block), compiled as `greedy_decode` compiles
    it: the Mamba2 state and conv stacks and the uses' KV stack are
    donated and aliased to the output, none padded (the state's 64-wide
    heads sit two a lane row), and the program holds no copy of any of
    them: no layer scan relays a stack out between the programs."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding

    from repro.configs import get_arch
    from repro.dist.sharding import named, params_pspecs
    from repro.models import build_model
    from repro.train.serve_loop import serving_jit

    mesh = Mesh(np.asarray(list(one_chip.device_set)).reshape(1, 1), ("data", "model"))
    cfg = dataclasses.replace(get_arch("zamba2-7b"), n_layers=12, hybrid_layer_ids=(6, 11))
    model = build_model(cfg, mesh=mesh)

    def sds(tree, shardings):
        return jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sh), tree, shardings)

    params = jax.eval_shape(lambda k: model.init(k, BF16), jax.random.PRNGKey(0))
    params = sds(params, named(mesh, params_pspecs(model, mesh)))
    cache = jax.eval_shape(lambda: model.init_cache(batch=32, s_max=769, dtype=BF16))
    cache = sds(cache, named(mesh, model.cache_pspecs(mesh, cache)))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    with force_pallas(True):
        compiled = serving_jit(model.decode_step).lower(
            params, jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=rep), cache,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(model.cache_nbytes(cache).values())
    state = cache["mamba"].state
    state_layer = state.size * state.dtype.itemsize // cfg.n_layers
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < state_layer // 4
    assert compiled.as_text().count("goldyloc_decode_attn") >= 2


@pytest.mark.parametrize("chunk", [t.bm for t in SCAN_TILES])
def test_ssd_scan_compiles(one_chip, chunk):
    B, T, H, P, N = 2, 1024, 8, 64, 128
    _compile(lambda xd, da, bm, cm: ssd_scan(xd, da, bm, cm, chunk=chunk,
                                             interpret=False),
             one_chip, ((B, T, H, P), jnp.float32), ((B, T, H), jnp.float32),
             ((B, T, H, N), jnp.float32), ((B, T, H, N), jnp.float32))
