"""The Mamba2 state's layout in HBM, as the scan and step kernels and the
model's decode cache hold it.

A row of the state holds ``hp`` heads side by side in its lanes:
(B, H/hp, N, hp·P), head k of a row in lanes [k·P, (k+1)·P).  64-wide
heads then fill the 128 lanes of a TPU tile in pairs instead of padding
each to 128, and the compiler keeps one layout for the stack across the
programs and the layer loops that read it (another would make it relayout
the whole stack between them).  A row never spans two B/C groups, so that
each row reads one group's B and C.
"""
from __future__ import annotations

import jax.numpy as jnp

LANES = 128   # lanes of a TPU vector tile


def heads_per_row(n_heads: int, head_dim: int, ngroups: int = 1) -> int:
    """Heads a row holds: as many ``head_dim``-wide heads as fill a lane
    tile, where they divide each group's heads; else 1."""
    hp = LANES // head_dim if LANES % head_dim == 0 else 1
    return hp if hp > 1 and (n_heads // max(ngroups, 1)) % hp == 0 else 1


def state_shape(batch: int, n_heads: int, d_state: int, head_dim: int,
                ngroups: int = 1) -> tuple:
    """Shape of one layer's state: (batch, rows, d_state, lanes)."""
    hp = heads_per_row(n_heads, head_dim, ngroups)
    return (batch, n_heads // hp, d_state, hp * head_dim)


def pack_state(s, hp: int):
    """(B,H,N,P) states in the (B,H/hp,N,hp·P) layout."""
    if hp == 1:
        return s
    B, H, N, P = s.shape
    return s.reshape(B, H // hp, hp, N, P).transpose(0, 1, 3, 2, 4).reshape(
        B, H // hp, N, hp * P)


def unpack_state(s, hp: int):
    """`pack_state`'s inverse."""
    if hp == 1:
        return s
    B, R, N, W = s.shape
    return s.reshape(B, R, N, hp, W // hp).transpose(0, 1, 3, 2, 4).reshape(
        B, R * hp, N, W // hp)


def over_lanes(t, rows: int, head_dim: int):
    """A per-head quantity (..., H) as (..., rows, hp·P) f32: each head's
    value over the lanes its state takes in a row."""
    hp = t.shape[-1] // rows
    t = t.astype(jnp.float32).reshape(*t.shape[:-1], rows, hp)
    return jnp.repeat(t, head_dim, axis=-1)
