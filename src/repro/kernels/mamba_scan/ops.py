"""Public SSD ops (fwd pallas / bwd via chunked-ref VJP).

``ssd_scan``         — general gated linear recurrence (powers mLSTM too).
``mamba_chunk_scan`` — Mamba2 layout (dt/A, group-shared B/C).
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
from repro.kernels.mamba_scan.ref import (
    _mamba_args,
    mamba_chunk_ref,
    ssd_chunk_ref,
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(xd, da, Bm, Cm, s0, chunk, interpret):
    Bsz, T, H, P = xd.shape
    N = Bm.shape[-1]
    Tp = -(-T // chunk) * chunk
    pad = Tp - T
    f32 = jnp.float32

    def prep(t, feat):  # (B,T,H,*) -> (B*H, Tp, *)
        t = jnp.pad(
            t.astype(f32), ((0, 0), (0, pad), (0, 0)) + ((0, 0),) * len(feat)
        )
        t = t.transpose(0, 2, 1, *range(3, 3 + len(feat)))
        return t.reshape(Bsz * H, Tp, *feat)

    xdf = prep(xd, (P,))
    daf = prep(da[..., None], (1,))
    Bf = prep(Bm, (N,))
    Cf = prep(Cm, (N,))
    y, s_final = mamba_scan_pallas(
        xdf, daf, Bf, Cf, s0.astype(f32).reshape(Bsz * H, N, P),
        chunk=chunk, interpret=interpret,
    )
    y = y.reshape(Bsz, H, Tp, P)[:, :, :T].transpose(0, 2, 1, 3)
    return y.astype(xd.dtype), s_final.reshape(Bsz, H, N, P)


def _ssd_fwd(xd, da, Bm, Cm, s0, chunk, interpret):
    return _ssd(xd, da, Bm, Cm, s0, chunk, interpret), (xd, da, Bm, Cm, s0)


def _ssd_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(
        lambda xd, da, Bm, Cm, s0: ssd_chunk_ref(
            xd, da, Bm, Cm, chunk=chunk, initial_state=s0),
        *res,
    )
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(
    xd, da, Bm, Cm,
    *,
    chunk: int = 128,
    initial_state=None,
    interpret: bool | None = None,
    force_ref: bool = False,
):
    """General SSD: xd (B,T,H,P); da (B,T,H); Bm/Cm (B,T,H,N);
    ``initial_state`` (B,H,N,P) or None for zeros.
    Returns (y, final_state)."""
    interp = bool(interpret)  # None → ref path off-TPU, pallas on TPU
    if force_ref or not (use_pallas() or interp):
        return ssd_chunk_ref(
            xd, da, Bm, Cm, chunk=chunk, initial_state=initial_state
        )
    if initial_state is None:
        Bsz, _, H, P = xd.shape
        initial_state = jnp.zeros((Bsz, H, Bm.shape[-1], P), jnp.float32)
    return _ssd(xd, da, Bm, Cm, initial_state, chunk, interp)


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
    interpret: bool | None = None,
    force_ref: bool = False,
):
    """Mamba2 SSD.  x (B,T,H,P); dt (B,T,H); A (H,); Bm/Cm (B,T,N)."""
    xd, da, Bh, Ch = _mamba_args(x, dt, A, Bm, Cm)
    y, S = ssd_scan(
        xd, da, Bh, Ch, chunk=chunk, initial_state=initial_state,
        interpret=interpret, force_ref=force_ref,
    )
    return y.astype(x.dtype), S
