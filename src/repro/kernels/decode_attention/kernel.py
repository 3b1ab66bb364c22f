"""Decode attention Pallas kernel: one new token per sequence against one
layer of a stacked KV cache, which it reads and updates in place.

The cache is the whole stack, (L, B, Hkv, hd, S), positions minor, S a
multiple of 128.  The layer and the token's position are scalar-prefetch
operands that only the index maps read, so each grid step DMAs its
(heads, hd, positions) block of one sequence straight out of the stacked
buffer, and the kernel writes back only the 128-position tile that takes
the new token (the K/V outputs alias the inputs): no layer of the cache is
sliced, copied, relaid out or written back whole.  q, o and the token's
own K/V come and go as (B, H·hd) rows, the layout of the projections that
make and take them.

Grid = (Hkv / hb, B, S / ts), the positions innermost: a step scores the
query heads of hb KV heads of one sequence against ts positions of the
cache, and an online softmax (flash decoding) carries the running max,
sum and weighted V across the position steps in VMEM, so the VMEM a step
holds does not grow with S.  A KV head's query heads are the rows of one
product against its K and V.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import VMEM_LIMIT_BYTES

NEG_INF = -1e30
BLOCK_BYTES = 4 * 2**20   # one K (or V) block; double-buffered K and V: 16 MiB
TILE = 128                # positions in the tile a token's write rewrites
NT = (((1,), (1,)), ((), ()))   # contract both operands' minor dims


def _decode_kernel(s_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                   o_ref, ko_ref, vo_ref, m_sc, l_sc, acc_sc, *, hb, rep, hd):
    b, t = pl.program_id(1), pl.program_id(2)
    pos, window = s_ref[1], s_ref[2]
    B, ts, cdt = q_ref.shape[0], k_ref.shape[-1], k_ref.dtype
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0) == b

    def row(ref):   # this sequence's row of a whole-batch block, (1, lanes)
        return jnp.sum(jnp.where(rows, ref[...].astype(jnp.float32), 0.0),
                       axis=0, keepdims=True).astype(cdt)

    q, kn, vn = row(q_ref), row(kn_ref), row(vn_ref)
    kpos = t * ts + jax.lax.broadcasted_iota(jnp.int32, (1, ts), 1)
    mask = jnp.logical_and(kpos < pos,
                           jnp.logical_or(window <= 0, kpos > pos - window))
    outs, new_kv = [], []
    for i in range(hb):
        # the query heads of KV head i, as the rows of one product: (rep, hd)
        qg = jnp.concatenate([q[:, (i * rep + j) * hd:(i * rep + j + 1) * hd]
                              for j in range(rep)], axis=0)
        k_new, v_new = kn[:, i * hd:(i + 1) * hd], vn[:, i * hd:(i + 1) * hd]
        new_kv.append((k_new, v_new))
        # The softmax starts from the token itself, which attends to its own
        # K and V; its score bounds the running max, so a masked position's
        # weight is exp(-1e30 - m) = 0.
        s_new = jnp.sum(qg.astype(jnp.float32) * k_new.astype(jnp.float32),
                        axis=-1, keepdims=True)                        # (rep, 1)
        first = t == 0
        m_prev = jnp.where(first, s_new, m_sc[i])
        l_prev = jnp.where(first, 1.0, l_sc[i])
        acc_prev = jnp.where(first, jnp.broadcast_to(
            v_new.astype(jnp.float32), (rep, hd)), acc_sc[i])
        s = jnp.dot(qg, k_ref[i], preferred_element_type=jnp.float32)  # (rep, ts)
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha, p = jnp.exp(m_prev - m), jnp.exp(s - m)
        l = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc_prev + jax.lax.dot_general(
            p.astype(cdt), v_ref[i], NT, preferred_element_type=jnp.float32)
        m_sc[i], l_sc[i], acc_sc[i] = m, l, acc
        o = acc / l                                                    # (rep, hd)
        outs += [o[j:j + 1] for j in range(rep)]

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        o = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)
        o_ref[...] = jnp.where(rows, o, o_ref[...])

    @pl.when(t == pos // ts)
    def _():
        # the token's K and V as columns, into their tile of the cache
        eye = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)).astype(jnp.float32)
        at = jax.lax.broadcasted_iota(jnp.int32, (hd, TILE), 1) == pos % TILE
        start = pl.multiple_of(pos % ts // TILE * TILE, TILE)
        for i, kv in enumerate(new_kv):
            for new, src, dst in zip(kv, (k_ref, v_ref), (ko_ref, vo_ref)):
                col = jax.lax.dot_general(eye, new.astype(jnp.float32), NT,
                                          preferred_element_type=jnp.float32)
                dst[i] = jnp.where(at, col.astype(cdt), src[i, :, pl.ds(start, TILE)])


def block_shape(hkv: int, hd: int, S: int, itemsize: int) -> tuple[int, int]:
    """(KV heads, positions) of a grid step's K block.  The heads: a
    divisor of ``hkv`` whose rows of hd lanes start on a lane tile (or all
    heads).  The positions: a multiple of 128 that divides ``S``, the most
    with which the fewest such heads fit `BLOCK_BYTES`; then the most heads
    that fit with them."""
    step = TILE // math.gcd(hd, TILE)
    ok = [d for d in range(1, hkv + 1)
          if hkv % d == 0 and (d % step == 0 or d == hkv)]
    nbytes = lambda d, ts: d * hd * ts * itemsize
    ts = max([ts for ts in range(TILE, S + 1, TILE)
              if S % ts == 0 and nbytes(ok[0], ts) <= BLOCK_BYTES], default=TILE)
    hb = max([d for d in ok if nbytes(d, ts) <= BLOCK_BYTES], default=ok[0])
    return hb, ts


def decode_attention_pallas(q, k_new, v_new, k, v, layer, pos, window=0, *,
                            interpret: bool = False):
    """q (B, Hq·hd), already scaled; k_new, v_new (B, Hkv·hd), the token's
    own K/V; k, v stacked (L, B, Hkv, hd, S), S a multiple of 128, row
    ``layer`` valid before position ``pos``; ``window`` > 0 (a scalar,
    traced or not) keeps the last ``window`` positions, the token's
    included.  Returns (o (B, Hq·hd) in q's dtype, k, v) with the token
    written at ``pos`` of row ``layer``."""
    B = q.shape[0]
    _, _, Hkv, hd, S = k.shape
    assert S % TILE == 0, f"cache capacity {S} is not a multiple of {TILE}"
    rep = q.shape[1] // (Hkv * hd)
    hb, ts = block_shape(Hkv, hd, S, k.dtype.itemsize)
    scalars = jnp.stack([jnp.asarray(x, jnp.int32) for x in (layer, pos, window)])
    rows = lambda w: pl.BlockSpec((B, w), lambda h, b, t, s: (0, h))
    cache = pl.BlockSpec((None, None, hb, hd, ts), lambda h, b, t, s: (s[0], b, h, 0, t))
    tile = pl.BlockSpec((None, None, hb, hd, TILE),
                        lambda h, b, t, s: (s[0], b, h, 0, s[1] // TILE))
    return pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb, rep=rep, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Hkv // hb, B, S // ts),
            in_specs=[rows(hb * rep * hd), rows(hb * hd), rows(hb * hd), cache, cache],
            out_specs=[rows(hb * rep * hd), tile, tile],
            scratch_shapes=[pltpu.VMEM((hb, rep, 1), jnp.float32),
                            pltpu.VMEM((hb, rep, 1), jnp.float32),
                            pltpu.VMEM((hb, rep, hd), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="goldyloc_decode_attn",
    )(scalars, q, k_new, v_new, k, v)
