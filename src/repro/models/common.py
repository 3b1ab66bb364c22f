"""Shared layers: RMSNorm, RoPE, SwiGLU MLP, embeddings, losses."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.spec import Spec


# ------------------------------------------------------------------ norms
def rms_norm_spec(d: int) -> Spec:
    return Spec((d,), ("embed",), init="ones")


def rms_norm(w, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x, positions, theta: float = 10_000.0):
    """x: (..., T, H, D) rotated pairwise; positions: (..., T) or (T,).

    The products by cos and sin run on the rows of all heads, (..., T, H·D),
    as the projections make them: per head, XLA lays the step's q and k
    out heads-major and relays out the projection weights to match, a
    copy of each weight every layer of every decode step."""
    *lead, H, D = x.shape
    freqs = rope_freqs(D, theta)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, D/2)
    cos = jnp.tile(jnp.cos(ang), 2 * H)  # (..., T, H·D)
    sin = jnp.tile(jnp.sin(ang), 2 * H)
    xf = x.reshape(*lead, H * D).astype(jnp.float32)
    xh = xf.reshape(x.shape)
    rot = jnp.concatenate([-xh[..., D // 2:], xh[..., : D // 2]], axis=-1)
    return (xf * cos + rot.reshape(xf.shape) * sin).astype(x.dtype).reshape(x.shape)


def head_rms_norm(w, x, eps: float = 1e-5):
    """`rms_norm` of each head of x (..., H, D), with the scaling on the
    rows of all heads (..., H·D), for the layout reason of `apply_rope`."""
    *lead, H, D = x.shape
    xf = x.reshape(*lead, H * D).astype(jnp.float32)
    var = jnp.mean(jnp.square(xf).reshape(*lead, H, D), axis=-1)
    s = jnp.repeat(jax.lax.rsqrt(var + eps), D, axis=-1)
    out = xf * s * jnp.tile(w.astype(jnp.float32), H)
    return out.astype(x.dtype).reshape(x.shape)


# ------------------------------------------------------------------- mlp
def mlp_specs(d: int, ff: int) -> dict:
    return {
        "gate": Spec((d, ff), ("embed", "mlp")),
        "up": Spec((d, ff), ("embed", "mlp")),
        "down": Spec((ff, d), ("mlp", "embed"), scale=0.5),
    }


def mlp_apply(p, x):
    h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
    return h @ p["down"]


# ------------------------------------------------------------- embeddings
def embed_specs(vocab: int, d: int, tie: bool) -> dict:
    """Untied lookup tables shard on d_model over 'model' (lookup gathers
    (B,T,D) activations instead of the whole table; embedding grads reduce
    per-slice — §Perf MoE iteration M3).  Tied tables stay vocab-sharded
    for the logits matmul."""
    if tie:
        return {"tok": Spec((vocab, d), ("vocab", "embed"), scale=1.0)}
    return {
        "tok": Spec((vocab, d), (None, "mlp"), scale=1.0),
        "head": Spec((d, vocab), ("embed", "vocab")),
    }


def embed_apply(p, tokens):
    return p["tok"][tokens]


def lm_head_apply(p, x):
    w = p.get("head")
    if w is None:
        w = p["tok"].T
    return x @ w


# ----------------------------------------------------------------- losses
def cross_entropy(logits, labels, mask=None):
    """Mean token NLL in f32; labels < 0 are ignored."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0 if mask is None else mask & (labels >= 0)
    lbl = jnp.maximum(labels, 0)
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, lbl[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    denom = jnp.maximum(valid.sum(), 1)
    return nll.sum() / denom
