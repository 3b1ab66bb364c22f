"""Public SSD ops (fwd pallas / bwd via chunked-ref VJP).

``ssd_scan``         — general gated linear recurrence (powers mLSTM too).
``mamba_chunk_scan`` — Mamba2 layout (dt/A, B/C shared by each group of
                       heads).
``scan_for_desc``    — execute the launch a `ScanDesc` (core/op_desc.py,
                       DESIGN.md §14) describes, with the GO-tuned chunk
                       length (TileConfig.bm) as the chunk axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import interpret_mode, use_pallas
from repro.kernels.mamba_scan.kernel import mamba_scan_pallas
from repro.kernels.mamba_scan.layout import pack_state, unpack_state
from repro.kernels.mamba_scan.ref import (
    _mamba_args,
    mamba_chunk_ref,
    per_head,
    ssd_chunk_ref,
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd(xd, da, Bm, Cm, s0, chunk, interpret, hp):
    """The kernel on (B,T,H,*) operands; ``s0`` and the final state in the
    (B, H/hp, N, hp·P) layout of `pack_state`."""
    Bsz, T, H, P = xd.shape
    G, N = Bm.shape[2], Bm.shape[-1]
    R = H // hp
    Tp = -(-T // chunk) * chunk
    pad = Tp - T
    f32 = jnp.float32

    def prep(t, rows, width):  # (B,T,rows·width) -> (B*rows, Tp, width)
        t = jnp.pad(t.astype(f32).reshape(Bsz, T, rows, width),
                    ((0, 0), (0, pad), (0, 0), (0, 0)))
        return t.transpose(0, 2, 1, 3).reshape(Bsz * rows, Tp, width)

    y, s_final = mamba_scan_pallas(
        prep(xd, R, hp * P), prep(da, R, hp), prep(Bm, G, N), prep(Cm, G, N),
        s0.astype(f32).reshape(Bsz * R, N, hp * P),
        chunk=chunk, rows_per_group=R // G, interpret=interpret,
    )
    y = y.reshape(Bsz, R, Tp, hp * P)[:, :, :T].transpose(0, 2, 1, 3)
    return (y.reshape(Bsz, T, H, P).astype(xd.dtype),
            s_final.reshape(Bsz, R, N, hp * P))


def _ssd_fwd(xd, da, Bm, Cm, s0, chunk, interpret, hp):
    return _ssd(xd, da, Bm, Cm, s0, chunk, interpret, hp), (xd, da, Bm, Cm, s0)


def _ssd_bwd(chunk, interpret, hp, res, g):
    H = res[0].shape[2]

    def ref(xd, da, Bm, Cm, s0):
        y, s = ssd_chunk_ref(xd, da, per_head(Bm, H), per_head(Cm, H), chunk=chunk,
                             initial_state=unpack_state(s0, hp))
        return y, pack_state(s, hp)

    _, vjp = jax.vjp(ref, *res)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(
    xd, da, Bm, Cm,
    *,
    chunk: int = 128,
    initial_state=None,
    heads_per_row: int = 1,
    interpret: bool | None = None,
    force_ref: bool = False,
):
    """General SSD: xd (B,T,H,P); da (B,T,H); Bm/Cm (B,T,G,N), head h
    reading group h // (H/G) (G = H: per head); ``initial_state`` or None
    for zeros.  Returns (y, final_state).  The states are (B,H,N,P), or
    with ``heads_per_row`` hp > 1 in `pack_state`'s (B,H/hp,N,hp·P)
    layout, the kernel's own."""
    interp = bool(interpret)  # None → ref path off-TPU, pallas on TPU
    Bsz, _, H, P = xd.shape
    hp = heads_per_row
    if force_ref or not (use_pallas() or interp):
        y, s = ssd_chunk_ref(
            xd, da, per_head(Bm, H), per_head(Cm, H), chunk=chunk,
            initial_state=None if initial_state is None
            else unpack_state(initial_state, hp),
        )
        return y, pack_state(s, hp)
    if initial_state is None:
        initial_state = jnp.zeros((Bsz, H // hp, Bm.shape[-1], hp * P), jnp.float32)
    return _ssd(xd, da, Bm, Cm, initial_state, chunk, interp, hp)


def scan_for_desc(
    desc, xd, da, Bm, Cm, *, tile=None, interpret: bool | None = None,
    force_ref: bool = False,
):
    """Execute the SSD-scan launch a `ScanDesc` describes (DESIGN.md §14).

    Operands follow `ssd_scan`'s general layout: xd (B,T,H,P), da (B,T,H),
    Bm/Cm (B,T,H,N).  ``tile.bm`` is the GO-tuned chunk length; it is
    clamped to the padded sequence so a decode step (T = 1) stays a
    single-chunk launch."""
    chunk = 128 if tile is None else max(8, min(int(tile.bm), 512))
    y, _ = ssd_scan(xd, da, Bm, Cm, chunk=chunk, interpret=interpret,
                    force_ref=force_ref)
    return y


def mamba_chunk_scan(
    x, dt, A, Bm, Cm,
    *,
    chunk: int = 128,
    initial_state=None,
    heads_per_row: int = 1,
    interpret: bool | None = None,
    force_ref: bool = False,
):
    """Mamba2 SSD.  x (B,T,H,P); dt (B,T,H); A (H,); Bm/Cm (B,T,N) or
    (B,T,G,N), each group shared by H/G consecutive heads; states as
    `ssd_scan`'s."""
    xd, da, Bg, Cg = _mamba_args(x, dt, A, Bm, Cm, per_heads=False)
    y, S = ssd_scan(
        xd, da, Bg, Cg, chunk=chunk, initial_state=initial_state,
        heads_per_row=heads_per_row, interpret=interpret, force_ref=force_ref,
    )
    return y.astype(x.dtype), S
