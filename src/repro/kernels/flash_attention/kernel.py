"""Blocked causal (optionally sliding-window) flash-attention Pallas kernel.

Grid = (B*Hq, q_blocks, kv_blocks); kv is the innermost sequential dim with
online-softmax state (m, l, acc) in VMEM scratch.  GQA is folded into the
index maps (q head -> kv head), so no repeated K/V materialization.  Fully
masked kv blocks (beyond the causal/window frontier) are skipped with
``pl.when`` — block-sparse causal iteration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import VMEM_LIMIT_BYTES

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *,
    n_kv: int,
    bq: int,
    bkv: int,
    scale: float,
    causal: bool,
    window: int,
    q_offset: int,
    s_len: int,
):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level causal frontier: first q position in this q block vs first
    # k position in this kv block.
    q_lo = iq * bq + q_offset
    k_lo = jk * bkv

    def _compute():
        # MXU operands stay in the storage dtype: bf16 products are exact
        # in the f32 accumulator, so the scores do not depend on the
        # backend's f32 matmul precision (and neither do `flash_ref`'s).
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (bq, bkv)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        msk = kpos < s_len
        if causal:
            msk = jnp.logical_and(msk, qpos >= kpos)
        if window:
            msk = jnp.logical_and(msk, qpos - kpos < window)
        s = jnp.where(msk, s, NEG_INF)

        m_prev = m_ref[...]                       # (bq, 128) replicated
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)           # (bq, 128)
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            p.sum(-1, keepdims=True), l_ref.shape
        )
        v = v_ref[0]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal or window:
        # Skip fully-masked kv blocks (block-sparse causal iteration).
        needed = jnp.asarray(True)
        if causal:
            needed = jnp.logical_and(needed, k_lo <= q_lo + bq - 1)
        if window:
            needed = jnp.logical_and(needed, k_lo + bkv - 1 >= q_lo - window + 1)
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(jk == n_kv - 1)
    def _done():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, T, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    q_offset: int = 0,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool = False,
):
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5

    Tp, Sp = -(-T // bq) * bq, -(-S // bkv) * bkv
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))

    qf = q.reshape(B * Hq, Tp, D)
    kf = k.reshape(B * Hkv, Sp, D)
    vf = v.reshape(B * Hkv, Sp, D)
    n_q, n_kv = Tp // bq, Sp // bkv

    def kv_index(bh, i, j):
        return ((bh // Hq) * Hkv + (bh % Hq) // rep, j, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            n_kv=n_kv, bq=bq, bkv=bkv, scale=scale,
            causal=causal, window=window, q_offset=q_offset, s_len=S,
        ),
        grid=(B * Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bkv, D), kv_index),
            pl.BlockSpec((1, bkv, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Tp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"goldyloc_flash_bq{bq}_bkv{bkv}",
    )(qf, kf, vf)
    return out.reshape(B, Hq, Tp, D)[:, :, :T]
