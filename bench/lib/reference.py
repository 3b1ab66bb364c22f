"""Plain reference of the served models, in float32 and jax.numpy only.

It imports nothing of the program.  It follows the equations the
program's configuration states (`run` in `bench/configs/<config>.json`),
which is what a served token has to agree with; where those depart from
the published model, the configuration file lists it under `assumed`.

A dense pre-norm decoder: RMSNorm; grouped-query attention with rotary
embedding over the whole head (halves rotated), optional RMSNorm of q and
k per head; SwiGLU MLP; untied output head.

Weights arrive in the program's tree layout (made by `weights.make`) and
are read one layer at a time, upcast to float32; every matrix product runs
at `Precision.HIGHEST`.  ``quant="fp8"`` is the control: every matrix
product of a linear layer takes both operands through float8 e4m3 with one
scale per row of the activations and one per column of the weights.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _fq(x, axis):
    """Fake quantization to float8 e4m3, absmax-scaled along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, quant):
    w = w.astype(F32)
    if quant == "fp8":
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, :, None, None].astype(F32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(p, h, m, quant):
    n, T, _ = h.shape
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _mm(h, p["wq"], quant).reshape(n, T, hq, hd)
    k = _mm(h, p["wk"], quant).reshape(n, T, hkv, hd)
    v = _mm(h, p["wv"], quant).reshape(n, T, hkv, hd)
    if m.get("qk_norm"):
        q = _rms(q, p["q_norm"], m["norm_eps"])
        k = _rms(k, p["k_norm"], m["norm_eps"])
    pos = jnp.broadcast_to(jnp.arange(T)[None], (n, T))
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)   # query head i reads kv head i // rep
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("nthd,nshd->nhts", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("nhts,nshd->nthd", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(n, T, hq * hd), p["wo"], quant)


def _dense_block(p, x, m, quant):
    eps = m["norm_eps"]
    x = x + _attention(p["attn"], _rms(x, p["attn_norm"], eps), m, quant)
    h = _rms(x, p["mlp_norm"], eps)
    g = _mm(h, p["mlp"]["gate"], quant)
    u = _mm(h, p["mlp"]["up"], quant)
    return x + _mm(jax.nn.silu(g) * u, p["mlp"]["down"], quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _layer(stack, x, i, m, quant):
    return _dense_block(jax.tree.map(lambda a: a[i], stack), x, m, quant)


@partial(jax.jit, static_argnames=("m", "start", "count", "quant"))
def _head(embed, final_norm, x, m, start, count, quant):
    x = _rms(x[:, start:start + count], final_norm, m["norm_eps"])
    return _mm(x, embed["head"], quant)


class _Frozen(dict):
    """A hashable model dict, so that it can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits(weights, model: dict, tokens, start: int, count: int, quant=None):
    """float32 logits at positions ``start .. start+count-1`` of every row
    of ``tokens`` (n, T); position t's logits predict token t+1."""
    m = _Frozen(model)
    x = jnp.take(weights["embed"]["tok"], jnp.asarray(tokens), axis=0).astype(F32)
    for i in range(model["n_layers"]):
        x = _layer(weights["layers"], x, i, m, quant)
    return _head(weights["embed"], weights["final_norm"], x, m, start, count, quant)
