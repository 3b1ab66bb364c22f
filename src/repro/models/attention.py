"""Attention variants: GQA (opt. QKV-bias / qk-norm / sliding window) and
DeepSeek-V2 MLA (latent-compressed KV, absorbed decode path).

Caches are fixed-capacity ring-less buffers (S_max slots); `length` is the
number of valid tokens.  Decode (T==1) uses a GEMV path against the cache;
MLA decode uses the *absorbed* formulation so the per-step cost scales with
the latent rank, not the expanded heads — mandatory at 32k/500k contexts.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.flash_attention import flash_attention
from repro.models.common import apply_rope, rms_norm
from repro.models.spec import Spec

NEG_INF = -1e30


def _pin_cache(x, mesh):
    """Pin a per-layer cache slice to (batch over DP, model-replicated or
    head-sharded) — prevents GSPMD from bouncing the multi-GB cache across
    the model axis every layer (§Perf decode iteration 2)."""
    if mesh is None:
        return x
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B = x.shape[0]
    while dp and B % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        dp = dp[:-1]
    spec = [dp if dp else None] + [None] * (x.ndim - 1)
    if x.ndim == 4 and mesh.shape.get("model", 1) > 1             and x.shape[2] % mesh.shape["model"] == 0:
        spec[2] = "model"  # kv heads
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec))
    )


# =========================================================== GQA attention
def gqa_specs(cfg: ArchConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    s = {
        "wq": Spec((d, hq * hd), ("embed", "heads")),
        "wk": Spec((d, hkv * hd), ("embed", "kv_heads")),
        "wv": Spec((d, hkv * hd), ("embed", "kv_heads")),
        "wo": Spec((hq * hd, d), ("heads", "embed"), scale=0.5),
    }
    if cfg.qkv_bias:
        s["bq"] = Spec((hq * hd,), ("heads",), init="zeros")
        s["bk"] = Spec((hkv * hd,), ("kv_heads",), init="zeros")
        s["bv"] = Spec((hkv * hd,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = Spec((hd,), (None,), init="ones")
        s["k_norm"] = Spec((hd,), (None,), init="ones")
    return s


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, Hkv, hd)
    v: jax.Array
    # length is tracked by the caller (shared across layers)


def init_kv_cache(cfg: ArchConfig, batch: int, s_max: int, dtype) -> KVCache:
    hd = cfg.resolved_head_dim
    shape = (batch, s_max, cfg.n_kv_heads, hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def gqa_apply(
    p: dict,
    x: jax.Array,               # (B, T, D)
    cfg: ArchConfig,
    positions: jax.Array,       # (B, T) absolute positions
    window: int = 0,
    cache: Optional[KVCache] = None,
    cache_len: Optional[jax.Array] = None,  # scalar current length
    mesh=None,
):
    B, T, D = x.shape
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads

    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, hq, hd)
    k = k.reshape(B, T, hkv, hd)
    v = v.reshape(B, T, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = _flash_heads(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            mesh,
            causal=True,
            window=window,
        ).transpose(0, 2, 1, 3)
        new_cache = None
    else:
        # Reshard the (tiny) new-token K/V to the cache's batch-only layout
        # BEFORE the write: otherwise GSPMD propagates the TP sharding of
        # the projection into the multi-GB cache and re-gathers it every
        # layer (§Perf decode iteration 4 — the winning move).
        k = _pin_batch_only(k.astype(cache.k.dtype), mesh)
        v = _pin_batch_only(v.astype(cache.v.dtype), mesh)
        with jax.named_scope("kv_write"):
            kc = jax.lax.dynamic_update_slice_in_dim(cache.k, k, cache_len, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache.v, v, cache_len, axis=1)
        kc, vc = _pin_cache(kc, mesh), _pin_cache(vc, mesh)
        new_cache = KVCache(kc, vc)
        if T > 1:
            # Prefill: flash attention against the written cache buffer —
            # the dense GEMV path would materialize O(T·S) scores
            # (§Perf prefill iteration 1).
            out = _flash_heads(
                q.transpose(0, 2, 1, 3),
                kc.transpose(0, 2, 1, 3),
                vc.transpose(0, 2, 1, 3),
                mesh,
                causal=True,
                window=window,
                q_offset=0,  # prefill starts at position 0
            ).transpose(0, 2, 1, 3)
        else:
            out = _attend_cache(
                q, kc, vc, q_pos=positions, length=cache_len + T,
                window=window, mesh=mesh,
            )
    y = out.reshape(B, T, hq * hd) @ p["wo"]
    return y, new_cache


def _attend_cache(q, kc, vc, *, q_pos, length, window, mesh=None):
    """Decode/verify attention against a fixed-size cache (GEMV path).

    q (B,T,Hq,hd); kc/vc (B,S,Hkv,hd); q_pos (B,T); length = valid tokens.
    The cache stays in its storage dtype (bf16) with f32 *accumulation*
    only, and the score einsum is pinned batch-sharded: replicating the
    tiny GEMV over the model axis is far cheaper than GSPMD's alternative
    of head-sharding + re-gathering the multi-GB cache every layer
    (§Perf decode iterations 2–3).
    """
    B, T, Hq, hd = q.shape
    S, Hkv = kc.shape[1], kc.shape[2]
    rep = Hq // Hkv
    qf = (q * (hd ** -0.5)).astype(kc.dtype)
    qf = qf.reshape(B, T, Hkv, rep, hd)
    s = jnp.einsum(
        "bthrd,bshd->bthrs", qf, kc, preferred_element_type=jnp.float32
    )
    s = _pin_batch_only(s, mesh)
    kpos = jnp.arange(S)
    mask = kpos[None, None, :] < length
    mask &= q_pos[..., None] >= kpos[None, None, :]
    if window:
        mask &= q_pos[..., None] - kpos[None, None, :] < window
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    out = jnp.einsum(
        "bthrs,bshd->bthrd", pattn, vc, preferred_element_type=jnp.float32
    )
    out = _pin_batch_only(out, mesh)
    return out.reshape(B, T, Hq, hd).astype(q.dtype)


def _pin_batch_only(x, mesh):
    if mesh is None:
        return x
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B = x.shape[0]
    while dp and B % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        dp = dp[:-1]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp if dp else None,
                                 *([None] * (x.ndim - 1))))
    )


def _flash_heads(q, k, v, mesh, **kw):
    """`flash_attention` on (B, H, T, D) operands with the heads sharded
    over 'model': one shard_map program per head shard, because the
    partitioner cannot split the kernel's custom call (and replicating
    the attention over the model axis would repeat its FLOPs on every
    device, §Perf train iteration T1).  Heads are independent and each
    shard keeps the same q-per-kv ratio, so GQA maps stay local."""
    if mesh is None or mesh.shape.get("model", 1) <= 1:
        return flash_attention(q, k, v, **kw)
    import numpy as np
    from jax.sharding import PartitionSpec as P

    msize = mesh.shape["model"]
    if q.shape[1] % msize or k.shape[1] % msize:
        return flash_attention(q, k, v, **kw)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B = q.shape[0]
    while dp and B % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        dp = dp[:-1]
    spec = P(dp if dp else None, "model", None, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, **kw), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )(q, k, v)


# =========================================================== MLA attention
def mla_specs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    h = cfg.n_heads
    r = cfg.kv_lora_rank
    dr, dn, dv = cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim
    s: dict = {
        "wdkv": Spec((d, r + dr), ("embed", None)),
        "kv_norm": Spec((r,), (None,), init="ones"),
        "wuk": Spec((r, h * dn), (None, "heads")),
        "wuv": Spec((r, h * dv), (None, "heads")),
        "wo": Spec((h * dv, d), ("heads", "embed"), scale=0.5),
    }
    if cfg.q_lora_rank:
        s["wdq"] = Spec((d, cfg.q_lora_rank), ("embed", None))
        s["q_norm"] = Spec((cfg.q_lora_rank,), (None,), init="ones")
        s["wuq"] = Spec((cfg.q_lora_rank, h * (dn + dr)), (None, "heads"))
    else:
        s["wq"] = Spec((d, h * (dn + dr)), ("embed", "heads"))
    return s


class MLACache(NamedTuple):
    ckv: jax.Array    # (B, S_max, r)
    krope: jax.Array  # (B, S_max, dr)


def init_mla_cache(cfg: ArchConfig, batch: int, s_max: int, dtype) -> MLACache:
    return MLACache(
        jnp.zeros((batch, s_max, cfg.kv_lora_rank), dtype),
        jnp.zeros((batch, s_max, cfg.qk_rope_head_dim), dtype),
    )


def _mla_q(p, x, cfg, positions):
    B, T, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = rms_norm(p["q_norm"], x @ p["wdq"], cfg.norm_eps)
        q = cq @ p["wuq"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, T, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_apply(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array,
    cache: Optional[MLACache] = None,
    cache_len: Optional[jax.Array] = None,
    mesh=None,
):
    B, T, D = x.shape
    h = cfg.n_heads
    r, dn, dr, dv = (
        cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim,
    )
    scale = (dn + dr) ** -0.5

    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    ckv_full = x @ p["wdkv"]
    ckv = rms_norm(p["kv_norm"], ckv_full[..., :r], cfg.norm_eps)
    krope = apply_rope(
        ckv_full[..., r:][:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]  # single shared rope head (B, T, dr)

    if cache is None or T > 1:
        # Training / prefill: expand latents to per-head K/V (standard
        # path, flash kernel).  Prefill (cache given, cache_len==0) also
        # writes the latent cache — the absorbed dense path would
        # materialize O(T·S) scores (§Perf prefill iteration 1).
        k_nope = (ckv @ p["wuk"]).reshape(B, T, h, dn)
        v = (ckv @ p["wuv"]).reshape(B, T, h, dv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope[:, :, None, :], (B, T, h, dr))],
            axis=-1,
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = _flash_heads(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            mesh,
            causal=True,
            scale=scale,
        ).transpose(0, 2, 1, 3)
        if cache is not None:
            ckv_w = _pin_batch_only(ckv.astype(cache.ckv.dtype), mesh)
            krope_w = _pin_batch_only(krope.astype(cache.krope.dtype), mesh)
            with jax.named_scope("kv_write"):
                new_cache = MLACache(
                    jax.lax.dynamic_update_slice_in_dim(
                        cache.ckv, ckv_w, cache_len, axis=1
                    ),
                    jax.lax.dynamic_update_slice_in_dim(
                        cache.krope, krope_w, cache_len, axis=1
                    ),
                )
        else:
            new_cache = None
    else:
        # Absorbed decode: score/value directly in latent space.  New-token
        # latents resharded to the cache layout before the write (see GQA).
        ckv_w = _pin_batch_only(ckv.astype(cache.ckv.dtype), mesh)
        krope_w = _pin_batch_only(krope.astype(cache.krope.dtype), mesh)
        with jax.named_scope("kv_write"):
            ckv_c = jax.lax.dynamic_update_slice_in_dim(
                cache.ckv, ckv_w, cache_len, axis=1
            )
            krope_c = jax.lax.dynamic_update_slice_in_dim(
                cache.krope, krope_w, cache_len, axis=1
            )
        ckv_c, krope_c = _pin_cache(ckv_c, mesh), _pin_cache(krope_c, mesh)
        new_cache = MLACache(ckv_c, krope_c)
        wuk = p["wuk"].reshape(r, h, dn)
        # q absorbed into latent space: (B,T,h,r)
        q_lat = jnp.einsum("bthn,rhn->bthr", q_nope.astype(jnp.float32),
                           wuk.astype(jnp.float32))
        s = jnp.einsum("bthr,bsr->bths", q_lat, ckv_c.astype(jnp.float32))
        s += jnp.einsum(
            "bthd,bsd->bths", q_rope.astype(jnp.float32),
            krope_c.astype(jnp.float32),
        )
        s *= scale
        S = ckv_c.shape[1]
        kpos = jnp.arange(S)
        mask = kpos[None, None, :] < (cache_len + T)
        mask &= positions[..., None] >= kpos[None, None, :]
        s = jnp.where(mask[:, :, None, :], s, NEG_INF)
        pattn = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bths,bsr->bthr", pattn, ckv_c.astype(jnp.float32))
        wuv = p["wuv"].reshape(r, h, dv)
        out = jnp.einsum(
            "bthr,rhd->bthd", o_lat, wuv.astype(jnp.float32)
        ).astype(x.dtype)

    y = out.reshape(B, T, h * dv) @ p["wo"]
    return y, new_cache
