"""Attention oracles.

``mha_ref``   — dense O(T²) attention; the numerical oracle for tests.
``flash_ref`` — chunked online-softmax attention (lax.scan over KV blocks),
                differentiable, O(T·bkv) memory; the CPU / dry-run path and
                the source of the backward pass for the pallas forward.

Layouts: q (B, Hq, T, D); k, v (B, Hkv, S, D); GQA via Hq % Hkv == 0.
``window > 0`` = sliding-window (local) causal attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask(qpos, kpos, causal: bool, window: int):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


def mha_ref(q, k, v, *, causal=True, window=0, scale=None, q_offset=0):
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    scale = scale if scale is not None else D ** -0.5
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    qpos = jnp.arange(T) + q_offset
    kpos = jnp.arange(S)
    m = _mask(qpos, kpos, causal, window)
    s = jnp.where(m[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhts,bhsd->bhtd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def flash_ref(q, k, v, *, causal=True, window=0, scale=None, q_offset=0,
              block_kv=512):
    """Online-softmax attention, scanned over KV blocks."""
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    Dv = v.shape[-1]  # MLA-style dv may differ from dqk
    scale = scale if scale is not None else D ** -0.5
    rep = Hq // Hkv
    nkv = -(-S // block_kv)
    Sp = nkv * block_kv
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    kb = k.reshape(B, Hkv, nkv, block_kv, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, Hkv, nkv, block_kv, Dv).transpose(2, 0, 1, 3, 4)
    qpos = jnp.arange(T) + q_offset

    # Same roundings as the kernel: scores from storage-dtype operands
    # (exact products, f32 accumulation), probabilities cast to v's dtype.
    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kblk, vblk, j = blk
        kpos = j * block_kv + jnp.arange(block_kv)
        krep = jnp.repeat(kblk, rep, axis=1)  # (B, Hq, bkv, D)
        s = jnp.einsum(
            "bhtd,bhsd->bhts", q, krep, preferred_element_type=jnp.float32
        ) * scale
        msk = jnp.ones((T, block_kv), bool)
        if causal:
            msk &= qpos[:, None] >= kpos[None, :]
        if window:
            msk &= qpos[:, None] - kpos[None, :] < window
        msk &= (kpos < S)[None, :]
        s = jnp.where(msk[None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(-1)
        vrep = jnp.repeat(vblk, rep, axis=1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhts,bhsd->bhtd", p.astype(vrep.dtype), vrep,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    init = (
        jnp.full((B, Hq, T), NEG_INF, jnp.float32),
        jnp.zeros((B, Hq, T), jnp.float32),
        jnp.zeros((B, Hq, T, Dv), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        step, init, (kb, vb, jnp.arange(nkv))
    )
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)
