"""Operations and bytes of zamba2's work, computed from shapes alone.

Model sizes come from the `run` section of a configuration file (the dict
`reference_zamba2` reads); nothing here reads the program.  As in
`flops.py`, a FLOP is a multiply or an add, and bytes are the least a
call must move between HBM and the chip, at the dtype the program stores
each operand in: weights and the conv inputs in bf16, the SSM state in
f32, K/V in bf16 at the cache's capacity rounded up to 128 positions.
"""
from __future__ import annotations

BF16, F32 = 2, 4
KV_TILE = 128


def _dims(m: dict):
    d = m["d_model"]
    di = m["ssm_expand"] * d
    H = di // m["ssm_head_dim"]
    cc = di + 2 * m["ssm_ngroups"] * m["ssm_state"]
    return d, di, H, cc


def uses(m: dict) -> int:
    return len(m["hybrid_layer_ids"])


def mixer_params(m: dict) -> int:
    """One Mamba2 layer's weights: in_proj, conv and bias, dt_bias, A_log,
    D, gated norm, out_proj, and the layer's RMSNorm."""
    d, di, H, cc = _dims(m)
    return d * (di + cc + H) + (m["ssm_conv"] + 1) * cc + 3 * H + di + di * d + d


def block_params(m: dict) -> int:
    """One shared block: input norm, q/k/v/o, pre-MLP norm, gate/up/down."""
    d, hd = m["d_model"], m["head_dim"]
    din = 2 * d if m["concat_embed"] else d
    attn = din * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd + m["n_heads"] * hd * d
    return din + attn + d + 3 * d * m["d_ff"]


def use_params(m: dict) -> int:
    """A use's own weights: its linear and its MLP LoRA."""
    d, r = m["d_model"], m["adapter_rank"]
    return d * d + r * (d + 2 * m["d_ff"])


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"] + m["d_model"]


def capacity(s_max: int) -> int:
    return -(-s_max // KV_TILE) * KV_TILE


def cache_bytes(m: dict, batch: int, s_max: int) -> dict:
    """The served cache by kind: ``ssm`` (every layer's f32 state and bf16
    conv inputs), ``kv`` (the uses' K and V)."""
    d, di, H, cc = _dims(m)
    L = m["n_layers"]
    state = L * batch * H * m["ssm_state"] * m["ssm_head_dim"] * F32
    conv = L * batch * (m["ssm_conv"] - 1) * cc * BF16
    kv = 2 * uses(m) * batch * m["n_kv_heads"] * m["head_dim"] * capacity(s_max) * BF16
    return {"ssm": state + conv, "kv": kv}


def ssm_decode_bytes(m: dict, batch: int) -> int:
    """Bytes the decode step's Mamba2 layers must move: their weights once,
    the conv inputs and the SSM state read and written once."""
    return (BF16 * m["n_layers"] * mixer_params(m)
            + 2 * cache_bytes(m, batch, 1)["ssm"])


def decode_step_bytes(m: dict, batch: int, s_max: int) -> int:
    """Bytes a decode step must move: the Mamba2 layers' (`ssm_decode_bytes`),
    a shared block's weights at each use, the uses' own weights, the head,
    and the K/V cache read whole (the decode kernel reads every position
    up to the capacity)."""
    return (ssm_decode_bytes(m, batch)
            + BF16 * (uses(m) * (block_params(m) + use_params(m)) + head_params(m))
            + cache_bytes(m, batch, s_max)["kv"])


def _matmul_params(m: dict) -> int:
    """Weights one token multiplies through, the head left out (the norms
    multiply nothing)."""
    d, di, H, cc = _dims(m)
    norms = (2 * d if m["concat_embed"] else d) + d
    return (m["n_layers"] * (d * (di + cc + H) + di * d)
            + uses(m) * (block_params(m) - norms + use_params(m)))


def _ssm_token_flops(m: dict) -> int:
    """The recurrence and the conv for one token, every layer: decay, B ⊗ x
    and their sum over the state, C·s, the D term, the conv taps."""
    d, di, H, cc = _dims(m)
    state = H * m["ssm_state"] * m["ssm_head_dim"]
    return m["n_layers"] * (6 * state + 2 * di + 2 * m["ssm_conv"] * cc)


def token_flops(m: dict, context: int) -> int:
    """Forward FLOPs of one decoded token whose uses attend to ``context``
    positions, the output head included."""
    attn = 4 * uses(m) * m["n_heads"] * m["head_dim"] * context
    return (2 * (_matmul_params(m) + m["d_model"] * m["vocab_size"])
            + attn + _ssm_token_flops(m))


def ssd_chunk(batch: int, heads: int, groups: int, T: int, N: int, P: int, chunk: int):
    """(FLOPs, bytes) of one layer's chunked SSD scan over a T-token prompt
    (`goldyloc_mamba_scan_c<chunk>`): per chunk of each (sequence, head),
    C·Bᵀ, the masked decay product with x, C·S and the state update; each
    f32 operand once (B and C once per group), the state in and out."""
    Tp = -(-T // chunk) * chunk
    per = 2 * chunk * chunk * N + 2 * chunk * chunk * P + 4 * chunk * N * P
    flops = batch * heads * (Tp // chunk) * per
    nbytes = F32 * (batch * heads * Tp * (2 * P + 1) + 2 * batch * groups * Tp * N
                    + 2 * batch * heads * N * P)
    return flops, nbytes


def prefill_flops(m: dict, prompt: int, chunk: int = 128) -> int:
    """One sequence's prefill: every prompt token through the layers, the
    uses' causal attention triangles, each layer's chunked scan, and the
    head for the last token."""
    d, di, H, cc = _dims(m)
    attn = 4 * uses(m) * m["n_heads"] * m["head_dim"] * prompt * (prompt + 1) // 2
    scan, _ = ssd_chunk(1, H, m["ssm_ngroups"], prompt, m["ssm_state"], m["ssm_head_dim"],
                        chunk)
    conv = 2 * m["ssm_conv"] * cc * prompt
    return (2 * _matmul_params(m) * prompt + attn + m["n_layers"] * (scan + conv)
            + 2 * d * m["vocab_size"])


def served_flops(m: dict, prompt: int, out_len: int) -> int:
    """Model FLOPs of one request: its prefill, then each of its own
    tokens after the first (which the prefill gives)."""
    return prefill_flops(m, prompt) + sum(
        token_flops(m, prompt + j) for j in range(1, out_len))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])


def check_cache(m: dict, batch: int, s_max: int, served: dict) -> None:
    """Raise where the shape count differs from the served cache's bytes."""
    want = cache_bytes(m, batch, s_max)
    if {k: served.get(k) for k in want} != want:
        raise ValueError(f"flops_hybrid counts the cache as {want}, the served arrays "
                         f"hold {served}")
