"""Plain reference of zamba2, in float32 and jax.numpy only.

It imports nothing of the program.  It follows `transformers`'
`modeling_zamba2.py` (4.57) as the configuration's `run` section states
it; where the configuration departs from the published model, its file
lists it under `assumed`.

- Embedding, then ``n_layers`` Mamba2 layers.  Before each layer in
  ``hybrid_layer_ids`` a shared block runs (use k takes block
  k % ``num_mem_blocks``) on [stream ‖ embedding]: RMSNorm over both,
  multi-head attention with rotary embedding over the whole head (halves
  rotated) and scores scaled by ``attn_scale``, RMSNorm, the gated MLP
  (``hidden_act`` "gelu": the exact GELU) whose gate and up projections
  gain use k's LoRA, and use
  k's ``linear``.  The block has no residual: its output is added to the
  layer's input before the layer's RMSNorm.
- A Mamba2 layer: x + out_proj(norm(y, z)) with RMSNorm before it.  The
  in-projection gives z, the conv's channels (x, B, C) and dt; a causal
  depthwise conv and SiLU; dt = max(softplus(dt + dt_bias), ``ssm_dt_min``);
  the recurrence one position at a time, s ← exp(dt·A)·s + dt·(B ⊗ x) and
  y = C·s + D·x, B and C shared by the H/G heads of each of G groups
  (head h reads group h // (H/G)); y · silu(z) normalised per group.
- RMSNorm and the untied output head.

Weights arrive in the program's tree layout and are read one layer at a
time, upcast to float32; every matrix product runs at
`Precision.HIGHEST`.  ``quant="fp8"`` is the control: every linear
layer's product takes both operands through float8 e4m3, as in
`reference.py`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.lib.reference import F32, HI, _mm, _rms, _rope


class _Frozen(dict):
    """A hashable model dict (lists as tuples), so that it can be a static
    argument."""

    def __init__(self, model):
        super().__init__({k: tuple(v) if isinstance(v, list) else v
                          for k, v in model.items()})

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _attention(p, h, m, quant):
    n, T, _ = h.shape
    H, hd = m["n_heads"], m["head_dim"]
    q = _mm(h, p["wq"], quant).reshape(n, T, H, hd)
    k = _mm(h, p["wk"], quant).reshape(n, T, H, hd)
    v = _mm(h, p["wv"], quant).reshape(n, T, H, hd)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (n, T))
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    s = jnp.einsum("nthd,nshd->nhts", q, k, precision=HI) * m["attn_scale"]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("nhts,nshd->nthd", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(n, T, H * hd), p["wo"], quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _shared(block, use, x, emb, m, quant):
    """One use of a shared block: its output through the use's linear."""
    eps = m["norm_eps"]
    h = jnp.concatenate([x, emb], -1) if m["concat_embed"] else x
    h = _rms(h, block["input_norm"], eps)
    h = _rms(_attention(block["attn"], h, m, quant), block["ff_norm"], eps)
    g = _mm(h, block["mlp"]["gate"], quant)
    u = _mm(h, block["mlp"]["up"], quant)
    if m["adapter_rank"]:
        lo = _mm(_mm(h, use["adapter"]["down"], quant), use["adapter"]["up"], quant)
        g, u = g + lo[..., :m["d_ff"]], u + lo[..., m["d_ff"]:]
    out = _mm(jax.nn.gelu(g, approximate=False) * u, block["mlp"]["down"], quant)
    return _mm(out, use["linear"], quant)


def _mamba(p, x, m, quant):
    n, T, d = x.shape
    di = m["ssm_expand"] * d
    P, N, G, K = m["ssm_head_dim"], m["ssm_state"], m["ssm_ngroups"], m["ssm_conv"]
    H = di // P
    proj = _mm(x, p["in_proj"], quant)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * G * N], proj[..., 2 * di + 2 * G * N:]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(xp[:, i:i + T] * w[i] for i in range(K)) + p["conv_b"].astype(F32))
    xs = xbc[..., :di].reshape(n, T, H, P)
    Bm = jnp.repeat(xbc[..., di:di + G * N].reshape(n, T, G, N), H // G, axis=2)
    Cm = jnp.repeat(xbc[..., di + G * N:].reshape(n, T, G, N), H // G, axis=2)
    dt = jnp.maximum(jax.nn.softplus(dt + p["dt_bias"].astype(F32)), m["ssm_dt_min"])
    A = -jnp.exp(p["A_log"].astype(F32))
    D = p["D"].astype(F32)

    def step(s, inp):                       # s (n, H, N, P), one position
        xt, dtt, bt, ct = inp
        s = (s * jnp.exp(dtt * A)[..., None, None]
             + (dtt[..., None] * bt)[..., None] * xt[:, :, None])
        return s, jnp.einsum("nhk,nhkp->nhp", ct, s, precision=HI) + D[:, None] * xt

    seq = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((n, H, N, P), F32),
                        (seq(xs), seq(dt), seq(Bm), seq(Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(n, T, di) * jax.nn.silu(z)
    g = y.reshape(n, T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + m["norm_eps"])
    y = g.reshape(n, T, di) * p["norm"].astype(F32)
    return _mm(y, p["out_proj"], quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _layer(stack, x, add, i, m, quant):
    p = jax.tree.map(lambda a: a[i], stack)
    return x + _mamba(p["mamba"], _rms(x + add, p["norm"], m["norm_eps"]), m, quant)


@partial(jax.jit, static_argnames=("m", "start", "count", "quant"))
def _head(embed, final_norm, x, m, start, count, quant):
    x = _rms(x[:, start:start + count], final_norm, m["norm_eps"])
    return _mm(x, embed["head"], quant)


def logits(weights, model: dict, tokens, start: int, count: int, quant=None):
    """float32 logits at positions ``start .. start+count-1`` of every row
    of ``tokens`` (n, T); position t's logits predict token t+1."""
    m = _Frozen(model)
    hybrid = list(m["hybrid_layer_ids"])
    emb = jnp.take(weights["embed"]["tok"], jnp.asarray(tokens), axis=0).astype(F32)
    x = emb
    for i in range(m["n_layers"]):
        add = jnp.zeros_like(x)
        if i in hybrid:
            k = hybrid.index(i)
            add = _shared(weights["shared"][k % m["num_mem_blocks"]],
                          weights["hybrid"][k], x, emb, m, quant)
        x = _layer(weights["layers"], x, add, i, m, quant)
    return _head(weights["embed"], weights["final_norm"], x, m, start, count, quant)
