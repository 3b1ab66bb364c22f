"""Public decode-attention op: the Pallas kernel on TPU (or in interpret
mode), the reference elsewhere."""
from __future__ import annotations

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.dispatch import use_pallas


def decode_attention(q, k_new, v_new, k, v, layer, pos, window=0, *,
                     interpret: bool | None = None):
    """One new token per sequence: its K/V (B, Hkv·hd) written at ``pos``
    of row ``layer`` of the stacked cache k, v (L, B, Hkv, hd, S), and its
    query q (B, Hq·hd), already scaled, attending over that row's
    positions up to ``pos`` (the last ``window`` where ``window`` > 0).
    Returns (o (B, Hq·hd), k, v)."""
    if not (use_pallas() or interpret):
        return decode_attention_ref(q, k_new, v_new, k, v, layer, pos, window)
    return decode_attention_pallas(q, k_new, v_new, k, v, layer, pos, window,
                                   interpret=bool(interpret))
