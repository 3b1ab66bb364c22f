"""Operations and bytes of the work, computed from shapes alone.

Model sizes come from the `run` section of a configuration file (the same
dict the reference reads); nothing here reads the program.  A FLOP is a
multiply or an add; a multiply-add is two.  Bytes are the least a call
must move between HBM and the chip: every operand read once, every result
written once, at the dtype the program stores it in.
"""
from __future__ import annotations

BF16 = 2


def _attn_params(m: dict) -> int:
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return d * hq * hd * 2 + d * hkv * hd * 2


def _mlp_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through, the output head included."""
    return (m["n_layers"] * (_attn_params(m) + _mlp_params(m))
            + m["d_model"] * m["vocab_size"])


def weight_bytes_read(m: dict) -> int:
    """Weights a decode step must read, each matrix once; the embedding
    table is only gathered and is left out."""
    return BF16 * matmul_params(m)


def token_flops(m: dict, context: int) -> int:
    """Forward FLOPs of one token that attends to ``context`` positions
    (itself included), the output head included."""
    mm = matmul_params(m)
    return 2 * mm + 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def prefill_flops(m: dict, prompt: int) -> int:
    """One sequence's prefill: every prompt token through the layers, the
    causal attention triangle, and the output head for the last token."""
    mm = matmul_params(m) - m["d_model"] * m["vocab_size"]
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * prompt * (prompt + 1) // 2
    return 2 * mm * prompt + attn + 2 * m["d_model"] * m["vocab_size"]


def served_flops(m: dict, prompt: int, out_len: int) -> int:
    """Model FLOPs of one request: its prefill, then each of its own
    tokens after the first (which the prefill gives)."""
    return prefill_flops(m, prompt) + sum(
        token_flops(m, prompt + j) for j in range(1, out_len))


def cache_bytes(m: dict, batch: int, s_max: int) -> int:
    """The cache a decode step must read: keys and values of every layer
    at full capacity (the step reads the whole buffer)."""
    return 2 * m["n_layers"] * batch * s_max * m["n_kv_heads"] * m["head_dim"] * BF16


def decode_step_bytes(m: dict, batch: int, s_max: int, chips: int) -> float:
    """Bytes one chip must read in a decode step: its share of the weights
    and of the cache (both split evenly over a tensor-parallel mesh)."""
    return (weight_bytes_read(m) + cache_bytes(m, batch, s_max)) / chips


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])


def flash(batch: int, heads: int, kv_heads: int, T: int, hd: int):
    """(FLOPs, bytes) of causal attention over a T-token prompt."""
    pairs = T * (T + 1) // 2
    flops = 4 * batch * heads * hd * pairs
    nbytes = BF16 * batch * T * hd * (2 * heads + 2 * kv_heads)
    return flops, nbytes
