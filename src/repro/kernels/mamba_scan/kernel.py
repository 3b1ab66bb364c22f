"""Chunked Mamba2 (SSD) scan Pallas kernel.

TPU adaptation of the SSD algorithm: the sequence is blocked into chunks of
length L; intra-chunk terms are dense (L,L)·(L,P) matmuls on the MXU, the
inter-chunk recurrence carries an (N,P) state in VMEM scratch across the
sequential chunk grid dimension.  This turns an elementwise recurrence into
MXU work — the TPU-native way to make SSMs compute-bound.

A grid row holds ``hp`` heads side by side in its lanes: x, y and the state
are (.., hp·P) wide, head k in lanes [k·P, (k+1)·P), so 64-wide heads fill
the 128 lanes of a tile in pairs instead of padding each to 128.  The
state's HBM layout is then (rows, N, hp·P), `layout.py`'s.

Grid = (B*H/hp, n_chunks); chunk dim is 'arbitrary' (sequential) so the
state scratch persists across chunks of one (batch, row).  B and C may be
held per group of heads (Mamba2's ``ngroups``): row ``r // rows_per_group``
of their (B*G, T, N) arrays serves the rows of that group, so they are
never repeated per head in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import VMEM_LIMIT_BYTES


def _mamba_kernel(
    xd_ref,    # (1, L, hp·P)  dt * x
    da_ref,    # (1, L, hp)    dt * A  (log decay), a column per head
    b_ref,     # (1, L, N)
    c_ref,     # (1, L, N)
    s0_ref,    # (1, N, hp·P)  initial state
    y_ref,     # (1, L, hp·P)
    sout_ref,  # (1, N, hp·P)  final state
    state_ref,  # VMEM scratch (N, hp·P) f32
    *,
    n_chunks: int,
    L: int,
    hp: int,
    P: int,
):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    Bm = b_ref[0].astype(jnp.float32)
    Cm = c_ref[0].astype(jnp.float32)
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = ii >= jj
    # Inclusive prefix sums of every head's log-decays as one lower-
    # triangular matmul: Mosaic has no cumsum lowering.  HIGHEST keeps the
    # f32 log-decays exact enough to exponentiate.
    das = da_ref[0].astype(jnp.float32)                    # (L, hp)
    ss = jnp.dot(causal.astype(jnp.float32), das,
                 preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)      # (L, hp)
    G = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    for k in range(hp):
        lanes = slice(k * P, (k + 1) * P)
        xd = xd_ref[0, :, lanes].astype(jnp.float32)
        da = das[:, k:k + 1]                               # (L, 1)
        s = ss[:, k:k + 1]
        stot = jnp.sum(da, axis=0, keepdims=True)          # (1, 1)
        S_prev = state_ref[:, lanes]
        logdec = jnp.where(causal, s - s.T, -jnp.inf)
        Y = jnp.dot(G * jnp.exp(logdec), xd, preferred_element_type=jnp.float32)
        Y += jnp.exp(s) * jnp.dot(
            Cm, S_prev, preferred_element_type=jnp.float32
        )
        S_new = jnp.exp(stot) * S_prev + jnp.dot(
            Bm.T, jnp.exp(stot - s) * xd,
            preferred_element_type=jnp.float32,
        )
        state_ref[:, lanes] = S_new
        y_ref[0, :, lanes] = Y.astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _done():
        sout_ref[0] = state_ref[...].astype(sout_ref.dtype)


def mamba_scan_pallas(
    xd: jax.Array,   # (R, T, hp·P) — dt*x, hp heads a row, T multiple of chunk
    da: jax.Array,   # (R, T, hp) — dt*A, a column per head
    Bm: jax.Array,   # (R / rows_per_group, T, N)
    Cm: jax.Array,   # (R / rows_per_group, T, N)
    s0: jax.Array,   # (R, N, hp·P)
    *,
    chunk: int = 128,
    rows_per_group: int = 1,
    interpret: bool = False,
):
    R, T, W = xd.shape
    hp = da.shape[-1]
    N = Bm.shape[-1]
    assert T % chunk == 0 and W % hp == 0
    n_chunks = T // chunk
    rpg = rows_per_group
    assert Bm.shape[0] * rpg == R
    if rpg == 1:
        group = lambda b, c: (b, c, 0)
    else:
        group = lambda b, c: (b // rpg, c, 0)

    y, s_final = pl.pallas_call(
        functools.partial(_mamba_kernel, n_chunks=n_chunks, L=chunk, hp=hp, P=W // hp),
        grid=(R, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, W), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hp), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), group),
            pl.BlockSpec((1, chunk, N), group),
            pl.BlockSpec((1, N, W), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, W), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, W), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, T, W), xd.dtype),
            jax.ShapeDtypeStruct((R, N, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"goldyloc_mamba_scan_c{chunk}",
    )(xd, da, Bm, Cm, s0)
    return y, s_final
