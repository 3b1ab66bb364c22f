"""Attention variants: GQA (opt. QKV-bias / qk-norm / sliding window) and
DeepSeek-V2 MLA (latent-compressed KV, absorbed decode path).

Caches are fixed-capacity ring-less buffers (S_max slots), stacked over the
layers and updated in place: each layer writes its new tokens into its own
row and reads that row; `length` is the number of valid tokens.  Decode
(T==1) runs the decode-attention kernel against the stacked cache; MLA
decode uses the *absorbed* formulation so the per-step cost scales with
the latent rank, not the expanded heads — mandatory at 32k/500k contexts.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models.common import apply_rope, head_rms_norm, rms_norm
from repro.models.spec import Spec

NEG_INF = -1e30


def batch_axes(mesh, batch: int):
    """The data-parallel axes of ``mesh`` ('pod', 'data') over which
    ``batch`` rows split evenly, dropped from the last until they do;
    None where none is left."""
    import numpy as np

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    while dp and batch % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        dp = dp[:-1]
    return dp or None


def pin_cache(x, mesh):
    """Pin a stacked cache buffer, (L, B, Hkv, hd, S) for K/V or (L, B, S, r)
    for a latent, to batch over DP and KV heads over 'model' where they
    divide (`Model.cache_pspecs`' layout) — prevents GSPMD from bouncing
    the multi-GB cache across the model axis every layer (§Perf decode
    iteration 2)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None, batch_axes(mesh, x.shape[1])] + [None] * (x.ndim - 2)
    if x.ndim == 5 and mesh.shape.get("model", 1) > 1 \
            and x.shape[2] % mesh.shape["model"] == 0:
        spec[2] = "model"  # kv heads
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec))
    )


def _write_token(buf, new, layer, cache_len, axis):
    """``new`` written into the stacked ``buf`` at row ``layer``, from
    position ``cache_len`` along ``axis`` of one layer: one
    dynamic_update_slice, which XLA does in place on a carried buffer."""
    idx = [jnp.zeros((), jnp.int32)] * buf.ndim
    idx[0] = jnp.asarray(layer, jnp.int32)
    idx[axis + 1] = jnp.asarray(cache_len, jnp.int32)
    return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype)[None], idx)


def _layer(buf, layer):
    """Row ``layer`` of a stacked cache buffer, for an operand that reads it."""
    return jax.lax.dynamic_index_in_dim(buf, layer, axis=0, keepdims=False)


# =========================================================== GQA attention
def gqa_specs(cfg: ArchConfig) -> dict:
    d, hd = cfg.attn_in_dim, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    s = {
        "wq": Spec((d, hq * hd), ("embed", "heads")),
        "wk": Spec((d, hkv * hd), ("embed", "kv_heads")),
        "wv": Spec((d, hkv * hd), ("embed", "kv_heads")),
        "wo": Spec((hq * hd, cfg.d_model), ("heads", "embed"), scale=0.5),
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
    k: jax.Array  # (B, Hkv, hd, S): positions minor, the layout the decode
    v: jax.Array  # kernel reads (K transposed for q·K, V for p·Vᵀ)
    # length is tracked by the caller (shared across layers)


def init_kv_cache(cfg: ArchConfig, batch: int, s_max: int, dtype) -> KVCache:
    """A zero KV cache for ``s_max`` positions, rounded up to a multiple of
    128: whole lane tiles, in which the TPU keeps the buffer row-major, as
    the decode kernel reads it (at 769 positions, with 128-wide heads, it
    picks another layout, and XLA would relayout the whole cache every
    step)."""
    hd = cfg.resolved_head_dim
    shape = (batch, cfg.n_kv_heads, hd, -(-s_max // 128) * 128)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def gqa_apply(
    p: dict,
    x: jax.Array,               # (B, T, D)
    cfg: ArchConfig,
    positions: jax.Array,       # (B, T) absolute positions
    window: int = 0,
    cache: Optional[KVCache] = None,   # stacked: (L, B, Hkv, hd, S)
    cache_len: Optional[jax.Array] = None,  # scalar current length
    layer=None,                 # this layer's row of the stacked cache
    global_layer=None,          # traced bool: attend globally, not in window
    mesh=None,
):
    """GQA attention of one layer.  With a cache, the layer's new K/V are
    written into its row of the stacked buffer and the attention reads
    that row; the buffer comes back whole, updated in place."""
    B, T, D = x.shape
    hd, scale = cfg.resolved_head_dim, cfg.resolved_attn_scale
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
        q = head_rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = head_rms_norm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and T == 1:
        # Decode: the kernel writes the token's K/V into the layer's row and
        # attends over it; the window is a scalar to it, so gemma3's
        # local/global choice needs no cond around the stacked buffer.
        if global_layer is not None:
            window = jnp.where(global_layer, 0, window)
        out, kc, vc = _decode_heads(
            (q * scale).reshape(B, hq * hd), k.reshape(B, hkv * hd),
            v.reshape(B, hkv * hd), cache, layer, cache_len, window, mesh)
        new_cache = KVCache(pin_cache(kc, mesh), pin_cache(vc, mesh))
    else:
        if cache is not None:
            # Prefill, from position 0.  Reshard the prompt's K/V to the
            # cache's batch-only layout BEFORE the write: otherwise GSPMD
            # propagates the TP sharding of the projection into the
            # multi-GB cache and re-gathers it every layer (§Perf decode
            # iteration 4 — the winning move).
            with jax.named_scope("kv_write"):
                kc = _write_token(cache.k, _pin_batch_only(k.transpose(0, 2, 3, 1), mesh),
                                  layer, cache_len, axis=3)
                vc = _write_token(cache.v, _pin_batch_only(v.transpose(0, 2, 3, 1), mesh),
                                  layer, cache_len, axis=3)
            new_cache = KVCache(pin_cache(kc, mesh), pin_cache(vc, mesh))

        def attend(w):
            # Training, or prefill: its prompt's own K/V are all the cache
            # holds, so flash attention over them; the dense decode path
            # would materialize O(T·S) scores (§Perf prefill iteration 1).
            return _flash_heads(
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                mesh,
                causal=True,
                window=w,
                scale=scale,
            ).transpose(0, 2, 1, 3)

        if global_layer is None:
            out = attend(window)
        else:
            # the window must be static for the flash kernel: a cond over
            # the two static variants (gemma3's local:global pattern)
            out = jax.lax.cond(global_layer, lambda: attend(0),
                               lambda: attend(window))
    y = out.reshape(B, T, hq * hd) @ p["wo"]
    return y, new_cache


def _decode_heads(q, k_new, v_new, cache: KVCache, layer, pos, window, mesh):
    """`decode_attention` on (B, H·hd) rows and the stacked cache, with the
    heads sharded over 'model' as the cache holds them: one shard_map
    program per head shard, since the partitioner cannot split the
    kernel's custom call (see `_flash_heads`).  Returns (o, k, v)."""
    args = (q, k_new, v_new, cache.k, cache.v, layer, pos,
            jnp.asarray(window, jnp.int32))
    if mesh is None or mesh.shape.get("model", 1) <= 1:
        return decode_attention(*args)
    from jax.sharding import PartitionSpec as P

    if cache.k.shape[2] % mesh.shape["model"]:
        return decode_attention(*args)
    dp = batch_axes(mesh, q.shape[0])
    row, stack = P(dp, "model"), P(None, dp, "model", None, None)
    return jax.shard_map(
        decode_attention, mesh=mesh,
        in_specs=(row, row, row, stack, stack, P(), P(), P()),
        out_specs=(row, stack, stack), check_vma=False,
    )(*args)


def _pin_batch_only(x, mesh):
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(batch_axes(mesh, x.shape[0]),
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
    from jax.sharding import PartitionSpec as P

    msize = mesh.shape["model"]
    if q.shape[1] % msize or k.shape[1] % msize:
        return flash_attention(q, k, v, **kw)
    spec = P(batch_axes(mesh, q.shape[0]), "model", None, None)
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
    cache: Optional[MLACache] = None,  # stacked: (L, B, S_max, r | dr)
    cache_len: Optional[jax.Array] = None,
    layer=None,                        # this layer's row of the stacked cache
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

    new_cache = None
    if cache is not None:
        # New-token latents resharded to the cache layout before the write
        # (see GQA).
        ckv_w = _pin_batch_only(ckv, mesh)
        krope_w = _pin_batch_only(krope, mesh)
        with jax.named_scope("kv_write"):
            ckv_s = _write_token(cache.ckv, ckv_w, layer, cache_len, axis=1)
            krope_s = _write_token(cache.krope, krope_w, layer, cache_len, axis=1)
        new_cache = MLACache(pin_cache(ckv_s, mesh), pin_cache(krope_s, mesh))

    if cache is None or T > 1:
        # Training / prefill: expand latents to per-head K/V (standard
        # path, flash kernel); prefill has written the latent cache above —
        # the absorbed dense path would materialize O(T·S) scores (§Perf
        # prefill iteration 1).
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
    else:
        # Absorbed decode: score/value directly in latent space, reading
        # this layer's row of the written cache.
        ckv_c = _layer(new_cache.ckv, layer)
        krope_c = _layer(new_cache.krope, layer)
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
