"""SSD (chunked linear-recurrence) oracles.

General recurrence (per head, state N x P):
    S_t = exp(da_t) * S_{t-1} + B_t xd_t^T ;   y_t = C_t^T S_t
with xd = pre-scaled input (B,T,H,P), da = log-decay (B,T,H),
B/C per-head (B,T,H,N).  Mamba2 (da = dt*A, xd = dt*x, shared B/C) and
mLSTM (da = log f, xd = i*v, B = k, C = q) are both instances.

``ssd_scan_seq_ref``  — token-sequential; the numerical oracle.
``ssd_chunk_ref``     — chunked (matches the pallas kernel's algorithm),
                        differentiable; the CPU / dry-run path.
Both return (y (B,T,H,P), final_state (B,H,N,P)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan_seq_ref(xd, da, Bm, Cm, *, initial_state=None):
    Bsz, T, H, P = xd.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    xd, da, Bm, Cm = (t.astype(f32) for t in (xd, da, Bm, Cm))
    S0 = (
        jnp.zeros((Bsz, H, N, P), f32)
        if initial_state is None
        else initial_state.astype(f32)
    )

    def step(S, inp):
        xt, dat, bt, ct = inp  # (B,H,P) (B,H) (B,H,N) (B,H,N)
        a = jnp.exp(dat)
        S = S * a[..., None, None] + bt[..., :, None] * xt[..., None, :]
        y = jnp.einsum("bhn,bhnp->bhp", ct, S)
        return S, y

    xs = (
        xd.transpose(1, 0, 2, 3),
        da.transpose(1, 0, 2),
        Bm.transpose(1, 0, 2, 3),
        Cm.transpose(1, 0, 2, 3),
    )
    S, ys = jax.lax.scan(step, S0, xs)
    return ys.transpose(1, 0, 2, 3).astype(xd.dtype), S


def _chunk_body(S_prev, xd, da, Bm, Cm):
    """One chunk, one (batch, head): xd (L,P); da (L,); Bm/Cm (L,N)."""
    L = xd.shape[0]
    s = jnp.cumsum(da)                               # inclusive (L,)
    stot = s[-1]
    G = jnp.dot(Cm, Bm.T)                            # (L,L) C_i . B_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    logdec = jnp.where(ii >= jj, s[:, None] - s[None, :], -jnp.inf)
    Y = jnp.dot(G * jnp.exp(logdec), xd)             # intra-chunk
    Y += jnp.exp(s)[:, None] * jnp.dot(Cm, S_prev)   # inter-chunk
    S_new = jnp.exp(stot) * S_prev + jnp.dot(
        Bm.T, jnp.exp(stot - s)[:, None] * xd
    )
    return Y, S_new


def ssd_chunk_ref(xd, da, Bm, Cm, *, chunk=128, initial_state=None):
    Bsz, T, H, P = xd.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    Tp = -(-T // chunk) * chunk
    pad = Tp - T
    xf = jnp.pad(xd.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    daf = jnp.pad(da.astype(f32), ((0, 0), (0, pad), (0, 0)))
    Bf = jnp.pad(Bm.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    Cf = jnp.pad(Cm.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    nC = Tp // chunk

    def to_chunks(t, feat):  # (B,T,H,*) -> (nC, B, H, L, *)
        t = t.transpose(0, 2, 1, *range(3, 2 + len(feat) + 1))
        t = t.reshape(Bsz, H, nC, chunk, *feat)
        return t.transpose(2, 0, 1, 3, *range(4, 4 + len(feat)))

    xs = (to_chunks(xf, (P,)), to_chunks(daf[..., None], (1,))[..., 0],
          to_chunks(Bf, (N,)), to_chunks(Cf, (N,)))
    S0 = (
        jnp.zeros((Bsz, H, N, P), f32)
        if initial_state is None
        else initial_state.astype(f32)
    )

    def chunk_step(S, inp):
        xd_c, da_c, B_c, C_c = inp  # (B,H,L,*)
        Y, S_new = jax.vmap(jax.vmap(_chunk_body))(S, xd_c, da_c, B_c, C_c)
        return S_new, Y

    S, Ys = jax.lax.scan(chunk_step, S0, xs)
    # Ys: (nC, B, H, L, P) -> (B, Tp, H, P)
    y = Ys.transpose(1, 2, 0, 3, 4).reshape(Bsz, H, Tp, P).transpose(0, 2, 1, 3)
    return y[:, :T].astype(xd.dtype), S


# ----------------------------------------------------- mamba2 conveniences
def per_head(t, H):
    """B or C (B,T,G,N) repeated to (B,T,H,N): head h reads group h // (H/G)."""
    G = t.shape[2]
    return t if G == H else jnp.repeat(t, H // G, axis=2)


def _mamba_args(x, dt, A, Bm, Cm, per_heads=True):
    """(xd, da, B, C) of the general recurrence; Bm/Cm (B,T,N) (one group)
    or (B,T,G,N), repeated per head unless ``per_heads`` is False."""
    H = x.shape[2]
    f32 = jnp.float32
    xd = x.astype(f32) * dt.astype(f32)[..., None]
    da = dt.astype(f32) * A.astype(f32)[None, None, :]
    Bg, Cg = (t[:, :, None, :] if t.ndim == 3 else t for t in (Bm, Cm))
    if per_heads:
        Bg, Cg = per_head(Bg, H), per_head(Cg, H)
    return xd, da, Bg.astype(f32), Cg.astype(f32)


def mamba_scan_ref(x, dt, A, Bm, Cm, *, initial_state=None):
    y, S = ssd_scan_seq_ref(
        *_mamba_args(x, dt, A, Bm, Cm), initial_state=initial_state
    )
    return y.astype(x.dtype), S


def mamba_chunk_ref(x, dt, A, Bm, Cm, *, chunk=128, initial_state=None):
    y, S = ssd_chunk_ref(
        *_mamba_args(x, dt, A, Bm, Cm), chunk=chunk,
        initial_state=initial_state,
    )
    return y.astype(x.dtype), S
