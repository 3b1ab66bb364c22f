"""Stream-K persistent-grid GEMM kernel + ragged-shape bitwise epilogue
tests (DESIGN.md §15).

The bitwise trick: integer-valued f32 inputs with row sums far below
2^24 make every summation association *exact*, so any decomposition of
the MAC-iteration sequence — tile, split-K, Stream-K — must reproduce
`gemm_ref` bit-for-bit.  A dropped, double-counted, or misrouted
iteration (the classic fixup-pass bugs) shows up as a hard mismatch
instead of hiding inside an rtol.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gemm import TileConfig, gemm, gemm_ref, gemm_stream_k_ref
from repro.kernels.gemm.kernel import matmul_stream_k, stream_k_geometry

# Ragged on every axis: M/N not tile multiples, K not (bk·split) multiples.
RAGGED_SHAPES = [
    (8, 128, 1100),     # decode row, ragged K
    (130, 70, 96),      # ragged M/N, single k tile
    (257, 129, 384),    # ragged M/N, aligned K
    (48, 200, 520),     # everything ragged
]
TRANSPOSES = [(False, False), (False, True), (True, False), (True, True)]


def _int_valued(key, shape):
    """Integer-valued f32 in [-4, 4] — exact under any association."""
    return jax.random.randint(key, shape, -4, 5).astype(jnp.float32)


def _operands(seed, M, N, K, ta, tb):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    a = _int_valued(k1, (K, M) if ta else (M, K))
    b = _int_valued(k2, (N, K) if tb else (K, N))
    return a, b


# ---------------------------------------------------------------- geometry
def test_stream_k_geometry_partitions_all_iterations():
    """Every MAC iteration lands in exactly one workgroup span, and the
    per-tile contributor counts match the span arithmetic the fixup pass
    allocates slots from."""
    for tm, tn, tk, g in [(1, 1, 32, 8), (3, 2, 5, 7), (4, 4, 1, 16),
                          (2, 3, 7, 1), (5, 1, 3, 4)]:
        total, ipw, g_live, counts, slots = stream_k_geometry(tm, tn, tk, g)
        assert total == tm * tn * tk
        assert g_live == -(-total // ipw) and g_live <= max(1, min(g, total))
        # reconstruct contributor counts by brute force
        brute = np.zeros((tm, tn), np.int64)
        for q in range(tm * tn):
            gs = {(q * tk + j) // ipw for j in range(tk)}
            brute[q // tn, q % tn] = len(gs)
            assert max(gs) < g_live
        assert np.array_equal(brute, counts)
        assert slots == counts.max()


# ------------------------------------------------------------- the kernel
@pytest.mark.parametrize("grid_g", [1, 3, 5, 8])
@pytest.mark.parametrize("ta,tb", TRANSPOSES)
def test_stream_k_kernel_bitwise_vs_oracle(grid_g, ta, tb):
    """The persistent kernel + fixup pass is bitwise-equal to the plain
    XLA dot AND to the pure-Python span-walk mirror (aligned shapes —
    the kernel's own contract; ragged shapes go through `gemm`)."""
    M, N, K = 16, 256, 1024
    bm, bn, bk = 8, 128, 256
    a, b = _operands(grid_g * 41 + ta * 2 + tb, M, N, K, ta, tb)
    out = matmul_stream_k(a, b, ta=ta, tb=tb, bm=bm, bn=bn, bk=bk,
                          grid_g=grid_g, out_dtype=jnp.float32,
                          interpret=True)
    ref = gemm_ref(a, b, ta=ta, tb=tb, out_dtype=jnp.float32)
    mirror = gemm_stream_k_ref(a, b, bm=bm, bn=bn, bk=bk, grid_g=grid_g,
                               ta=ta, tb=tb, out_dtype=jnp.float32)
    assert jnp.array_equal(out, ref), (grid_g, ta, tb)
    assert jnp.array_equal(out, mirror), (grid_g, ta, tb)


@pytest.mark.parametrize("grid_g", [2, 7, 8])
@pytest.mark.parametrize("shape", RAGGED_SHAPES)
def test_gemm_stream_k_ragged_bitwise(shape, grid_g):
    """Acceptance (§15): the op-level Stream-K path (zero-padding + span
    walk + fixup + crop) is bitwise-equal to `gemm_ref` on shapes that
    are ragged against the tile on every axis."""
    M, N, K = shape
    a, b = _operands(M * 7 + grid_g, M, N, K, False, False)
    tile = TileConfig(64, 128, 128, stream_k=grid_g)
    out = gemm(a, b, tile=tile, interpret=True)
    ref = gemm_ref(a, b)
    assert out.shape == (M, N)
    assert jnp.array_equal(out, ref), (shape, grid_g)


def test_gemm_stream_k_vjp_matches_oracle():
    """Backward GEMMs inherit the Stream-K tile (dgrad/wgrad walk their
    own iteration spans)."""
    M, N, K = 32, 64, 512
    a, b = _operands(13, M, N, K, False, False)
    tile = TileConfig(32, 64, 64, stream_k=5)

    f = lambda a, b: (gemm(a, b, tile=tile, interpret=True) ** 2).sum()
    fr = lambda a, b: (gemm_ref(a, b) ** 2).sum()
    g = jax.grad(f, argnums=(0, 1))(a, b)
    gr = jax.grad(fr, argnums=(0, 1))(a, b)
    for x, y in zip(g, gr):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


def test_tile_config_stream_k_key_and_exclusivity():
    assert TileConfig(64, 128, 256, stream_k=8).key() == "64x128x256g8"
    assert TileConfig(64, 128, 256).stream_k == 0    # v2/v3 blobs default
    with pytest.raises(ValueError, match="mutually exclusive"):
        TileConfig(64, 128, 256, split_k=2, stream_k=8)
    # stream-K never changes the per-instance VMEM working set
    assert TileConfig(64, 128, 256, stream_k=8).vmem_bytes(2) == \
        TileConfig(64, 128, 256).vmem_bytes(2)


# ------------------------------------- ragged bitwise epilogue (satellite)
@pytest.mark.parametrize("mode", ["interpret", "force_ref"])
@pytest.mark.parametrize("split_k", [3, 4, 8])
@pytest.mark.parametrize("shape", RAGGED_SHAPES)
def test_gemm_split_k_ragged_bitwise(shape, split_k, mode):
    """Satellite (§13/§15): the split-K partial-accumulate + reduce
    epilogue is bitwise-exact on ragged shapes — K not divisible by the
    split factor, M/N not divisible by the tile — in interpret mode and
    on the force_ref path (which must agree because integer-valued
    inputs leave no association slack)."""
    M, N, K = shape
    a, b = _operands(M * 13 + split_k + (mode == "force_ref"),
                     M, N, K, False, False)
    tile = TileConfig(64, 128, 128, split_k=split_k)
    kw = (dict(interpret=True) if mode == "interpret"
          else dict(force_ref=True))
    out = gemm(a, b, tile=tile, **kw)
    ref = gemm_ref(a, b)
    assert out.shape == (M, N)
    assert jnp.array_equal(out, ref), (shape, split_k, mode)


@pytest.mark.parametrize("ta,tb", TRANSPOSES)
def test_gemm_plain_tile_ragged_bitwise(ta, tb):
    """The un-decomposed kernel passes the same bitwise bar on ragged
    shapes (guards the shared padding/crop plumbing)."""
    M, N, K = 130, 70, 96
    a, b = _operands(ta * 2 + tb + 99, M, N, K, ta, tb)
    out = gemm(a, b, ta=ta, tb=tb, tile=TileConfig(64, 64, 64),
               interpret=True)
    assert jnp.array_equal(out, gemm_ref(a, b, ta=ta, tb=tb)), (ta, tb)


@pytest.mark.parametrize("grid_g", [4, 5])
def test_stream_k_fixup_reads_each_tiles_count(grid_g):
    """The fixup pass reads its tile's own contributor count from the
    SMEM table: on a grid whose counts differ across both tile axes, the
    kernel is bitwise-equal to the span-walk oracle."""
    M, N, K = 24, 256, 640
    bm, bn, bk = 8, 128, 128
    counts = stream_k_geometry(M // bm, N // bn, K // bk, grid_g)[3]
    assert len(np.unique(counts)) > 1
    a, b = _operands(grid_g, M, N, K, False, False)
    out = matmul_stream_k(a, b, ta=False, tb=False, bm=bm, bn=bn, bk=bk,
                          grid_g=grid_g, out_dtype=jnp.float32,
                          interpret=True)
    mirror = gemm_stream_k_ref(a, b, bm=bm, bn=bn, bk=bk, grid_g=grid_g,
                               out_dtype=jnp.float32)
    assert jnp.array_equal(out, mirror)
    assert jnp.array_equal(out, gemm_ref(a, b, out_dtype=jnp.float32))
