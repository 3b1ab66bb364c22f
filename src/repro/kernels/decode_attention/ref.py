"""Pure-jnp oracle of the decode kernel: the CPU and dry-run path, and
what the kernel is tested against."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_attention_ref(q, k_new, v_new, k, v, layer, pos, window=0):
    """`decode_attention_pallas`' contract: the token's K/V written at
    ``pos`` of row ``layer`` of the stacked k, v (L, B, Hkv, hd, S), then
    q (B, Hq·hd), already scaled, attends over that row's positions up to
    ``pos`` (the last ``window`` of them where ``window`` > 0).  The cache
    stays in its storage dtype (bf16) with f32 accumulation only."""
    B = q.shape[0]
    _, _, Hkv, hd, S = k.shape
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32), zero, zero, zero, jnp.asarray(pos, jnp.int32))
    with jax.named_scope("kv_write"):
        k = jax.lax.dynamic_update_slice(k, k_new.astype(k.dtype).reshape(1, B, Hkv, hd, 1), at)
        v = jax.lax.dynamic_update_slice(v, v_new.astype(v.dtype).reshape(1, B, Hkv, hd, 1), at)
    kc = jax.lax.dynamic_index_in_dim(k, layer, axis=0, keepdims=False)
    vc = jax.lax.dynamic_index_in_dim(v, layer, axis=0, keepdims=False)
    qg = q.astype(k.dtype).reshape(B, Hkv, -1, hd)
    s = jnp.einsum("bhrd,bhds->bhrs", qg, kc, preferred_element_type=jnp.float32)
    kpos = jnp.arange(S)
    mask = (kpos <= pos) & ((window <= 0) | (kpos > pos - window))
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    o = jnp.einsum("bhrs,bhds->bhrd", p, vc, preferred_element_type=jnp.float32)
    return o.reshape(q.shape).astype(q.dtype), k, v
