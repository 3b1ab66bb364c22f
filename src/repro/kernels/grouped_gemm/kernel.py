"""Grouped / ragged GEMM Pallas kernels — the TPU expression of GOLDYLOC
concurrency.

A GPU runs N independent GEMM kernels on streams; a TPU core runs one kernel
at a time, so "concurrent GEMMs" become ONE pallas_call whose grid interleaves
tiles from all group members.  Resource sharing is then explicit:

* the members' in-flight tiles share VMEM (so per-member tiles must shrink as
  CD grows — exactly the paper's RC-tuned GO-kernel effect),
* their HBM streams interleave (bandwidth sharing),
* tail waves of one member overlap with another member's tiles (the paper's
  "fewer waves ⇒ better overlap" observation maps to grid-slot packing).

Two variants:

``grouped_matmul_pallas`` — G homogeneous GEMMs, stacked (G, M, K) × (G, K, N).
    Grid = (m, n, G, k): group is the *second-innermost* dim so consecutive
    grid steps alternate members at the same (i, j) tile — interleaved, not
    serialized, execution.

``ragged_matmul_pallas`` — heterogeneous row counts (MoE experts, hetero
    GEMMs §6.7): A is (sum_g M_g, K) with per-group row-block offsets passed
    as scalar-prefetch; B is (G, K, N).  Grid = (total_m_blocks, n, k); a
    block→group map drives B's index_map (megablocks-style).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import VMEM_LIMIT_BYTES

# --------------------------------------------------------------------------
# Homogeneous grouped GEMM
# --------------------------------------------------------------------------
def _grouped_kernel(a_ref, b_ref, c_ref, acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[0], b_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        c_ref[0] = acc_ref[...].astype(c_ref.dtype)


def grouped_matmul_pallas(
    a: jax.Array,  # (G, M, K)
    b: jax.Array,  # (G, K, N)
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype,
    interpret: bool = False,
):
    G, M, K = a.shape
    _, _, N = b.shape
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    n_m, n_n, n_k = M // bm, N // bn, K // bk

    return pl.pallas_call(
        functools.partial(_grouped_kernel, n_k=n_k),
        grid=(n_m, n_n, G, n_k),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, g, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, g, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, j, g, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"goldyloc_grouped_gemm_g{G}_{bm}x{bn}x{bk}",
    )(a, b)


# --------------------------------------------------------------------------
# Ragged grouped GEMM (MoE experts / heterogeneous-M groups)
# --------------------------------------------------------------------------
def _ragged_kernel(
    block_group,   # scalar-prefetch: (total_m_blocks,) int32, group per block
    a_ref,
    b_ref,
    c_ref,
    acc_ref,
    *,
    n_k: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


def ragged_matmul_pallas(
    a: jax.Array,            # (Mtotal, K) — rows grouped, each group bm-padded
    b: jax.Array,            # (G, K, N)
    block_group: jax.Array,  # (Mtotal // bm,) int32
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype,
    interpret: bool = False,
):
    Mtotal, K = a.shape
    G, _, N = b.shape
    assert Mtotal % bm == 0 and N % bn == 0 and K % bk == 0
    n_mb = Mtotal // bm
    n_n, n_k = N // bn, K // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_mb, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, bg: (i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, bg: (bg[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, bg: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mtotal, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"goldyloc_ragged_gemm_g{G}_{bm}x{bn}x{bk}",
    )(block_group, a, b)
