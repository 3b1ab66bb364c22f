"""Mamba2 mixer (zamba2 backbone): a chunked scan over a prompt, and a
one-token state update for decode.

B and C come in ``ssm_ngroups`` groups, each shared by H/G consecutive
heads, and the gated RMSNorm normalises each group's d_inner/G channels
on its own (zamba2's ``Zamba2RMSNormGated``).  The decode state lives in
a cache stacked over the layers (`MambaCache`, (L, ...) per leaf); a layer
reads its row and writes it back, so the served step updates the stacked
buffer in place.  The state takes the kernels' layout
(`kernels/mamba_scan/layout.py`): hp heads side by side in each row of
lanes, (B, H/hp, N, hp·P).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.dispatch import use_pallas
from repro.kernels.mamba_scan import mamba_chunk_scan
from repro.kernels.mamba_scan.layout import heads_per_row, over_lanes, state_shape
from repro.kernels.mamba_scan.step import mamba_step
from repro.models.spec import Spec


def mamba_specs(cfg: ArchConfig) -> dict:
    d, di = cfg.d_model, cfg.ssm_d_inner
    H, cc = cfg.ssm_n_heads, cfg.ssm_conv_channels

    def a_init(k, shape):
        return jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0))

    def dt_init(k, shape):
        u = jax.random.uniform(k, shape, minval=1e-3, maxval=1e-1)
        return jnp.log(jnp.expm1(u))  # softplus inverse

    return {
        # z (di), then x, B, C (the conv's channels), then dt (H)
        "in_proj": Spec((d, di + cc + H), ("embed", "mlp")),
        "conv_w": Spec((cfg.ssm_conv, cc), (None, "mlp"), scale=1.0),
        "conv_b": Spec((cc,), ("mlp",), init="zeros"),
        "dt_bias": Spec((H,), (None,), init="custom", custom=dt_init),
        "A_log": Spec((H,), (None,), init="custom", custom=a_init),
        "D": Spec((H,), (None,), init="ones"),
        "norm": Spec((di,), ("mlp",), init="ones"),
        "out_proj": Spec((di, d), ("mlp", "embed"), scale=0.5),
    }


class MambaCache(NamedTuple):
    conv: jax.Array   # (L, conv_width-1, B, conv_ch): trailing conv inputs
    state: jax.Array  # (L, *layout.state_shape) f32 SSM state


def init_mamba_cache(cfg: ArchConfig, batch: int, dtype, layers: int) -> MambaCache:
    """A zero cache for ``layers`` Mamba2 layers; the state is kept in f32."""
    shape = state_shape(batch, cfg.ssm_n_heads, cfg.ssm_state, cfg.ssm_head_dim,
                        cfg.ssm_ngroups)
    return MambaCache(
        jnp.zeros((layers, cfg.ssm_conv - 1, batch, cfg.ssm_conv_channels), dtype),
        jnp.zeros((layers, *shape), jnp.float32),
    )


def _causal_conv(x, w, b, prefix):
    """Depthwise causal conv in f32.  x (B,T,C); w (k,C); prefix (k-1,B,C),
    the cache's layout, or None for zeros.  Returns the conv's output and
    the last k-1 inputs, (k-1,B,C) (the next prefix).  The inputs are
    taken one position at a time, so neither the prefix nor the tail is
    transposed: a transpose of a row would lead the compiler to relayout
    the whole stacked buffer around it."""
    k, T = w.shape[0], x.shape[1]
    if prefix is None:
        prefix = jnp.zeros((k - 1, x.shape[0], x.shape[2]), x.dtype)
    taps = [prefix[j].astype(x.dtype) for j in range(k - 1)]
    if T == 1:
        seq = taps + [x[:, 0]]
        out = sum(seq[i].astype(jnp.float32) * w[i].astype(jnp.float32) for i in range(k))
        out = out[:, None]
    else:
        xp = jnp.concatenate([jnp.stack(taps, axis=1), x], axis=1)
        seq = taps + [x[:, t] for t in range(max(0, T - (k - 1)), T)]
        out = sum(xp[:, i:i + T].astype(jnp.float32) * w[i].astype(jnp.float32)
                  for i in range(k))
    return out + b.astype(jnp.float32), jnp.stack(seq[-(k - 1):], axis=0)


def ssm_step(state, x, dt, A, Bm, Cm, D):
    """One token of the Mamba2 recurrence, in f32: the decode update off
    the TPU (on it, `mamba_step`'s kernel, which reads the state once
    where XLA's fusions of this read it twice).

    state (B, H/hp, N, hp·P), hp heads a row (`layout`); x (B,H,P); dt
    (B,H), already through softplus; A, D (H,); Bm/Cm (B,G,N), head h
    reading group h // (H/G).  Returns y = C·s' + D·x (B,H,P) and
    s' = exp(dt·A)·s + dt·(B ⊗ x)."""
    Bsz, R, N, W = state.shape
    H, P, G = x.shape[1], x.shape[2], Bm.shape[1]
    f32 = jnp.float32
    xw = x.astype(f32).reshape(Bsz, R, W)
    dtw = over_lanes(dt, R, P)
    Bp = jnp.repeat(Bm.astype(f32), R // G, axis=1)            # (B,R,N)
    Cp = jnp.repeat(Cm.astype(f32), R // G, axis=1)
    new = (state * jnp.exp(dtw * over_lanes(A, R, P))[:, :, None, :]
           + Bp[..., None] * (dtw * xw)[:, :, None, :])
    y = jnp.einsum("brn,brnw->brw", Cp, new) + over_lanes(D, R, P) * xw
    return y.reshape(Bsz, H, P), new


def gated_rms_norm(w, y, z, groups: int, eps: float):
    """RMSNorm of y · silu(z), each of ``groups`` channel groups on its own,
    in f32; scaled by ``w`` and cast to z's dtype."""
    f32 = jnp.float32
    h = y.astype(f32) * jax.nn.silu(z.astype(f32))
    g = h.reshape(*h.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(h.shape) * w.astype(f32)).astype(z.dtype)


def _pin_ssd_heads(t, mesh, axis):
    """Pin the SSM head dim over 'model' — GSPMD otherwise replicates the
    chunked scan across the model axis (§Perf train iteration T2)."""
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return t
    msize = mesh.shape["model"]
    if msize <= 1 or t.shape[axis] % msize != 0:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = [None] * t.ndim
    spec[axis] = "model"
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, P(*spec)))


def _row(buf, layer):
    return jax.lax.dynamic_index_in_dim(buf, layer, axis=0, keepdims=False)


def _write_row(buf, new, layer):
    idx = [jnp.asarray(layer, jnp.int32)] + [jnp.zeros((), jnp.int32)] * (buf.ndim - 1)
    return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype)[None], idx)


def mamba_apply(
    p: dict,
    x: jax.Array,  # (B, T, D)
    cfg: ArchConfig,
    cache: Optional[MambaCache] = None,
    layer=None,
    mesh=None,
):
    """Returns (y, cache).  With a cache (stacked over the layers), the
    layer writes its state after ``x`` into its row ``layer``: a single
    token (T = 1) updates the state the row holds; a prompt starts the
    sequence (prefill from position 0, as `gqa_apply`'s) and runs the
    chunked scan from zeros, reading nothing of the row.  cache=None:
    training, from zeros."""
    B, T, _ = x.shape
    di, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads, cfg.ssm_head_dim
    G, cc = cfg.ssm_ngroups, cfg.ssm_conv_channels
    z, xbc, dt = jnp.split(x @ p["in_proj"], [di, di + cc], axis=-1)
    decode = cache is not None and T == 1
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dt = jnp.maximum(jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)),
                     cfg.ssm_dt_min)
    if decode:
        with jax.named_scope("ssm_update"):
            conv, tail = _causal_conv(xbc, p["conv_w"], p["conv_b"], _row(cache.conv, layer))
            xc, Bm, Cm = jnp.split(jax.nn.silu(conv)[:, 0], [di, di + G * N], axis=-1)
            step = (xc.reshape(B, H, P), dt[:, 0], A, Bm.reshape(B, G, N),
                    Cm.reshape(B, G, N), p["D"])
            if use_pallas():
                y, states = mamba_step(cache.state, layer, *step)
            else:
                y, state = ssm_step(_row(cache.state, layer), *step)
                states = _write_row(cache.state, state, layer)
            y = y.reshape(B, 1, di)
    else:
        with jax.named_scope("ssd_scan"):
            conv, tail = _causal_conv(xbc, p["conv_w"], p["conv_b"], None)
            xc, Bm, Cm = jnp.split(jax.nn.silu(conv).astype(x.dtype), [di, di + G * N],
                                   axis=-1)
            xh = _pin_ssd_heads(xc.reshape(B, T, H, P), mesh, 2)
            dt = _pin_ssd_heads(dt, mesh, 2)
            y, state = mamba_chunk_scan(xh, dt, A, Bm.reshape(B, T, G, N),
                                        Cm.reshape(B, T, G, N),
                                        heads_per_row=heads_per_row(H, P, G))
            states = None if cache is None else _write_row(cache.state, state, layer)
            y = y.astype(jnp.float32) + (p["D"].astype(jnp.float32)[:, None]
                                         * xh.astype(jnp.float32))
            y = y.reshape(B, T, di)
    out = gated_rms_norm(p["norm"], y, z, G, cfg.norm_eps) @ p["out_proj"]
    if cache is None:
        return out, None
    return out, MambaCache(_write_row(cache.conv, tail, layer), states)
