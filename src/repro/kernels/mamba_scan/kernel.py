"""Chunked Mamba2 (SSD) scan Pallas kernel.

TPU adaptation of the SSD algorithm: the sequence is blocked into chunks of
length L; intra-chunk terms are dense (L,L)·(L,P) matmuls on the MXU, the
inter-chunk recurrence carries an (N,P) state in VMEM scratch across the
sequential chunk grid dimension.  This turns an elementwise recurrence into
MXU work — the TPU-native way to make SSMs compute-bound.

Grid = (B*H, n_chunks); chunk dim is 'arbitrary' (sequential) so the state
scratch persists across chunks of one (batch, head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import VMEM_LIMIT_BYTES

def _mamba_kernel(
    xd_ref,    # (1, L, P)  dt * x
    da_ref,    # (1, L, 1)  dt * A  (log decay), one column per chunk
    b_ref,     # (1, L, N)
    c_ref,     # (1, L, N)
    s0_ref,    # (1, N, P)  initial state
    y_ref,     # (1, L, P)
    sout_ref,  # (1, N, P)  final state
    state_ref,  # VMEM scratch (N, P) f32
    *,
    n_chunks: int,
    L: int,
):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    xd = xd_ref[0].astype(jnp.float32)
    da = da_ref[0].astype(jnp.float32)   # (L, 1)
    Bm = b_ref[0].astype(jnp.float32)
    Cm = c_ref[0].astype(jnp.float32)
    S_prev = state_ref[...]

    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = ii >= jj
    # Inclusive prefix sum as a lower-triangular matmul: Mosaic has no
    # cumsum lowering.  HIGHEST keeps the f32 log-decays exact enough to
    # exponentiate.
    s = jnp.dot(causal.astype(jnp.float32), da,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)       # (L, 1)
    stot = jnp.sum(da, axis=0, keepdims=True)              # (1, 1)
    G = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    logdec = jnp.where(causal, s - s.T, -jnp.inf)
    Y = jnp.dot(G * jnp.exp(logdec), xd, preferred_element_type=jnp.float32)
    Y += jnp.exp(s) * jnp.dot(
        Cm, S_prev, preferred_element_type=jnp.float32
    )
    S_new = jnp.exp(stot) * S_prev + jnp.dot(
        Bm.T, jnp.exp(stot - s) * xd,
        preferred_element_type=jnp.float32,
    )
    state_ref[...] = S_new
    y_ref[0] = Y.astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _done():
        sout_ref[0] = S_new.astype(sout_ref.dtype)


def mamba_scan_pallas(
    xd: jax.Array,   # (BH, T, P) — dt*x, T multiple of chunk
    da: jax.Array,   # (BH, T, 1) — dt*A, a column so every chunk's block
                     #   (1, chunk, 1) meets the TPU block-shape rule
    Bm: jax.Array,   # (BH, T, N)
    Cm: jax.Array,   # (BH, T, N)
    s0: jax.Array,   # (BH, N, P)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    BH, T, P = xd.shape
    N = Bm.shape[-1]
    assert T % chunk == 0
    n_chunks = T // chunk

    y, s_final = pl.pallas_call(
        functools.partial(_mamba_kernel, n_chunks=n_chunks, L=chunk),
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, P), xd.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"goldyloc_mamba_scan_c{chunk}",
    )(xd, da, Bm, Cm, s0)
    return y, s_final
