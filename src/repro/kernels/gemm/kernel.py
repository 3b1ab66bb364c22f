"""Tiled GEMM Pallas kernel with an explicitly tunable BlockSpec tiling.

This is the object of GOLDYLOC's tuning: the (bm, bn, bk) tile config decides
VMEM working set (the TPU analogue of LDS+occupancy), HBM traffic (the
paper's "global memory requests"), and wave count (#grid tiles / pipeline
slots).  The isolated-tuned and GO (resource-constrained) variants of a GEMM
are *this same kernel* instantiated with different TileConfigs.

Grid = (m_tiles, n_tiles, k_tiles); k is the innermost, sequential
("arbitrary") dimension accumulating into an f32 VMEM scratch tile.

**Split-K** (``split_k > 1``, DESIGN.md §13): the K sweep is partitioned
into ``split_k`` contiguous slices, grid = (split, m, n, k/split).  Each
slice accumulates its own f32 *partial* C block into a (split, M, N)
scratch output, and a second pallas kernel — the reduce epilogue — sums
the partials and casts to the output dtype.  This multiplies the number
of parallel grid tiles by ``split_k``, recovering pipeline occupancy for
skinny GEMMs whose (m, n) grid is a single tile.

**Stream-K** (``matmul_stream_k``, DESIGN.md §15): the *work-centric*
generalization.  The global MAC-iteration sequence — output tiles in
(m-major, n, k-minor) order, ``total = tm·tn·tk`` block-dot steps — is
chopped into ``G`` equal contiguous spans, one per *persistent*
workgroup, so the grid size is a free knob (the tuner sets it to the
CD-derated core budget) instead of a quantity quantized by the output
shape.  A workgroup finishing mid-tile emits an f32 partial; a fixup
pass — the split-K reduce epilogue generalized with a per-tile
contributor count and an iota mask — reconciles the ≤ G-1 straddled
tiles.  Split-K is the special case where every span covers whole tiles
of one K slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import VMEM_LIMIT_BYTES

def _matmul_kernel(a_ref, b_ref, c_ref, acc_ref, *, n_k: int, ta: bool, tb: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if ta:
        a = a.T  # stored (bk, bm) -> (bm, bk)
    if tb:
        b = b.T  # stored (bn, bk) -> (bk, bn)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


def _matmul_splitk_kernel(a_ref, b_ref, p_ref, acc_ref, *, n_ks: int,
                          ta: bool, tb: bool):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if ta:
        a = a.T  # stored (bk, bm) -> (bm, bk)
    if tb:
        b = b.T  # stored (bn, bk) -> (bk, bn)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == n_ks - 1)
    def _done():
        p_ref[...] = acc_ref[...][None]  # f32 partial for this K slice


def _reduce_kernel(p_ref, o_ref):
    o_ref[...] = p_ref[...].sum(axis=0).astype(o_ref.dtype)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    ta: bool,
    tb: bool,
    bm: int,
    bn: int,
    bk: int,
    out_dtype,
    split_k: int = 1,
    interpret: bool = False,
):
    """C[M,N] = op(a) @ op(b).

    Storage shapes: ``a`` is (M,K), or (K,M) when ``ta``; ``b`` is (K,N), or
    (N,K) when ``tb`` (the paper's default B layout).  All dims must already
    be padded to tile multiples (ops.py does this); for ``split_k > 1`` the
    K dim must be padded to a ``bk * split_k`` multiple so every K slice
    sweeps the same number of k tiles.
    """
    if ta:
        K, M = a.shape
    else:
        M, K = a.shape
    if tb:
        N, Kb = b.shape
    else:
        Kb, N = b.shape
    assert K == Kb, (a.shape, b.shape, ta, tb)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_m, n_n, n_k = M // bm, N // bn, K // bk

    if split_k > 1:
        assert n_k % split_k == 0, (n_k, split_k)
        n_ks = n_k // split_k
        a_spec = (
            pl.BlockSpec((bk, bm), lambda s, i, j, k: (s * n_ks + k, i))
            if ta
            else pl.BlockSpec((bm, bk), lambda s, i, j, k: (i, s * n_ks + k))
        )
        b_spec = (
            pl.BlockSpec((bn, bk), lambda s, i, j, k: (j, s * n_ks + k))
            if tb
            else pl.BlockSpec((bk, bn), lambda s, i, j, k: (s * n_ks + k, j))
        )
        kernel = functools.partial(_matmul_splitk_kernel, n_ks=n_ks,
                                   ta=ta, tb=tb)
        partials = pl.pallas_call(
            kernel,
            grid=(split_k, n_m, n_n, n_ks),
            in_specs=[a_spec, b_spec],
            out_specs=pl.BlockSpec((1, bm, bn), lambda s, i, j, k: (s, i, j)),
            out_shape=jax.ShapeDtypeStruct((split_k, M, N), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
                dimension_semantics=(
                    "arbitrary", "parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
            name=f"goldyloc_gemm_{bm}x{bn}x{bk}s{split_k}",
        )(a, b)
        # Reduce epilogue: sum the f32 partials, cast to the output dtype.
        return pl.pallas_call(
            _reduce_kernel,
            grid=(n_m, n_n),
            in_specs=[pl.BlockSpec((split_k, bm, bn), lambda i, j: (0, i, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
                dimension_semantics=("parallel", "parallel"),
            ),
            interpret=interpret,
            name=f"goldyloc_gemm_reduce_{bm}x{bn}s{split_k}",
        )(partials)

    a_spec = (
        pl.BlockSpec((bk, bm), lambda i, j, k: (k, i))
        if ta
        else pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    )
    b_spec = (
        pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))
        if tb
        else pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    )

    kernel = functools.partial(_matmul_kernel, n_k=n_k, ta=ta, tb=tb)
    return pl.pallas_call(
        kernel,
        grid=(n_m, n_n, n_k),
        in_specs=[a_spec, b_spec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"goldyloc_gemm_{bm}x{bn}x{bk}",
    )(a, b)


# ------------------------------------------------------------------ Stream-K
def stream_k_geometry(tm: int, tn: int, tk: int, grid_g: int):
    """Static Stream-K launch geometry.

    Returns ``(total, ipw, g_live, counts, slots)``: the global MAC
    iteration count ``total = tm·tn·tk``, iterations per workgroup
    ``ipw = ⌈total / G⌉``, the live workgroup count ``⌈total / ipw⌉``
    (never a fully-dead workgroup), the per-output-tile contributor-count
    array (tm, tn) the fixup pass masks with, and the partial-slot depth
    ``slots = max(counts)``.  Pure Python/NumPy over static shapes —
    shared by the launcher, the ops-layer dispatch, and the pure-Python
    reference so all three walk identical spans."""
    total = tm * tn * tk
    ipw = -(-total // max(1, min(grid_g, total)))
    g_live = -(-total // ipw)
    q = np.arange(tm * tn, dtype=np.int64)
    g_first = (q * tk) // ipw
    g_last = ((q + 1) * tk - 1) // ipw
    counts = (g_last - g_first + 1).astype(np.int32).reshape(tm, tn)
    return total, ipw, g_live, counts, int(counts.max())


def _stream_k_kernel(a_ref, b_ref, p_ref, acc_ref, *, total: int, ipw: int,
                     tk: int, ta: bool, tb: bool):
    """One grid step = one global MAC iteration i = g·ipw + j.

    The accumulator resets at every tile frontier inside the span
    (``k == 0``) and at the span start (``j == 0``, possibly mid-tile);
    iterations past ``total`` (only in the last workgroup) contribute
    zero and re-write the final tile's finished partial — their block
    indices are clamped to iteration ``total - 1``, so the revisit is a
    no-op."""
    g = pl.program_id(0)
    j = pl.program_id(1)
    i = g * ipw + j
    live = i < total

    @pl.when(jnp.logical_or(jnp.logical_and(live, i % tk == 0), j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if ta:
        a = a.T  # stored (bk, bm) -> (bm, bk)
    if tb:
        b = b.T  # stored (bn, bk) -> (bk, bn)
    prod = jnp.dot(a, b, preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.where(live, prod, 0.0)
    # Flushed to HBM when the (slot, m, n) block index changes — i.e. at
    # tile frontiers and at the end of the span.
    p_ref[...] = acc_ref[...][None]


def _stream_k_fixup_kernel(counts_ref, p_ref, o_ref, *, slots: int):
    """Masked generalization of `_reduce_kernel`: per tile, sum the first
    ``counts`` partial slots (the rest were never written) and cast.
    ``counts_ref`` is the whole (tm, tn) table in SMEM: a (1, 1) VMEM
    block would break the TPU's (8, 128) block-shape rule."""
    cnt = counts_ref[pl.program_id(0), pl.program_id(1)]
    mask = jax.lax.broadcasted_iota(jnp.int32, (slots, 1, 1), 0) < cnt
    o_ref[...] = jnp.where(mask, p_ref[...], 0.0).sum(axis=0).astype(o_ref.dtype)


def matmul_stream_k(
    a: jax.Array,
    b: jax.Array,
    *,
    ta: bool,
    tb: bool,
    bm: int,
    bn: int,
    bk: int,
    grid_g: int,
    out_dtype,
    interpret: bool = False,
):
    """C[M,N] = op(a) @ op(b) via the Stream-K persistent-grid kernel.

    ``grid_g`` is the target workgroup count (the tuner's CD-derated core
    budget); the launch uses ``min(grid_g, total)`` live workgroups, each
    walking ``⌈total / G⌉`` contiguous MAC iterations.  Storage layouts
    match `matmul_pallas`; all dims must already be padded to plain tile
    multiples (no ``bk · split`` constraint — ragged spans are the point).
    """
    if ta:
        K, M = a.shape
    else:
        M, K = a.shape
    if tb:
        N, Kb = b.shape
    else:
        Kb, N = b.shape
    assert K == Kb, (a.shape, b.shape, ta, tb)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    tm, tn, tk = M // bm, N // bn, K // bk
    total, ipw, g_live, counts, slots = stream_k_geometry(tm, tn, tk, grid_g)

    def _q_k(g, j):
        i = jnp.minimum(g * ipw + j, total - 1)
        q = i // tk
        return q, i - q * tk

    def _a_map(g, j):
        q, k = _q_k(g, j)
        return (k, q // tn) if ta else (q // tn, k)

    def _b_map(g, j):
        q, k = _q_k(g, j)
        return (q % tn, k) if tb else (k, q % tn)

    def _p_map(g, j):
        i = jnp.minimum(g * ipw + j, total - 1)
        q = i // tk
        return g - (q * tk) // ipw, q // tn, q % tn

    a_spec = pl.BlockSpec((bk, bm) if ta else (bm, bk), _a_map)
    b_spec = pl.BlockSpec((bn, bk) if tb else (bk, bn), _b_map)
    kernel = functools.partial(_stream_k_kernel, total=total, ipw=ipw,
                               tk=tk, ta=ta, tb=tb)
    partials = pl.pallas_call(
        kernel,
        grid=(g_live, ipw),
        in_specs=[a_spec, b_spec],
        out_specs=pl.BlockSpec((1, bm, bn), _p_map),
        out_shape=jax.ShapeDtypeStruct((slots, M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            # both dims sequential: one persistent walk per workgroup
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=f"goldyloc_gemm_{bm}x{bn}x{bk}g{g_live}",
    )(a, b)
    return pl.pallas_call(
        functools.partial(_stream_k_fixup_kernel, slots=slots),
        grid=(tm, tn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((slots, bm, bn), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name=f"goldyloc_gemm_fixup_{bm}x{bn}g{g_live}",
    )(jnp.asarray(counts), partials)
