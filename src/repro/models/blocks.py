"""Block assembly: dense transformer, MoE transformer, zamba2 hybrid,
xLSTM groups — all shaped for lax.scan over layer stacks."""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.attention import (
    KVCache,
    MLACache,
    gqa_apply,
    gqa_specs,
    init_kv_cache,
    init_mla_cache,
    mla_apply,
    mla_specs,
)
from repro.models.common import mlp_apply, mlp_specs, rms_norm, rms_norm_spec
from repro.models.moe import moe_capacity_apply, moe_ep_apply, moe_specs
from repro.models.spec import Spec
from repro.models.ssm import mamba_apply, mamba_specs
from repro.models.xlstm import (
    MLSTMCache,
    SLSTMCache,
    init_mlstm_cache,
    init_slstm_cache,
    mlstm_apply,
    mlstm_specs,
    slstm_apply,
    slstm_specs,
)


# ==================================================== dense / moe blocks
def attn_block_specs(cfg: ArchConfig, d_ff: int, moe: bool) -> dict:
    s = {
        "attn_norm": rms_norm_spec(cfg.d_model),
        "mlp_norm": rms_norm_spec(cfg.d_model),
        "attn": mla_specs(cfg) if cfg.attn_type == "mla" else gqa_specs(cfg),
    }
    if moe:
        s["moe"] = moe_specs(cfg)
    else:
        s["mlp"] = mlp_specs(cfg.d_model, d_ff)
    return s


def attn_block_apply(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    positions: jax.Array,
    *,
    moe: bool,
    window: int = 0,
    global_layer=None,
    cache=None,
    cache_len=None,
    layer=None,
    mesh=None,
    moe_mode: str = "auto",
    moe_capacity_factor: float = 1.25,
):
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    with jax.named_scope("attn"):
        if cfg.attn_type == "mla":
            a, new_cache = mla_apply(
                p["attn"], h, cfg, positions, cache=cache, cache_len=cache_len,
                layer=layer, mesh=mesh,
            )
        else:
            a, new_cache = gqa_apply(
                p["attn"], h, cfg, positions, window=window,
                cache=cache, cache_len=cache_len, layer=layer,
                global_layer=global_layer, mesh=mesh,
            )
    x = x + a
    h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("mlp"):
        if moe:
            # EP dispatch shards tokens over the model axis; at decode (T == 1,
            # indivisible) the cheap capacity path runs instead (GSPMD shards the
            # expert einsum over E and inserts the combine collectives).
            use_ep = moe_mode == "ep" or (
                moe_mode == "auto" and mesh is not None
                and "model" in mesh.axis_names and mesh.shape["model"] > 1
                and h.shape[1] % mesh.shape["model"] == 0
            )
            if use_ep:
                m, aux = moe_ep_apply(
                    p["moe"], h, cfg, mesh,
                    capacity_factor=moe_capacity_factor,
                    data_axes=tuple(a for a in mesh.axis_names if a != "model"),
                )
            else:
                m, aux = moe_capacity_apply(
                    p["moe"], h, cfg, capacity_factor=moe_capacity_factor
                )
        else:
            m = mlp_apply(p["mlp"], h)
    return x + m, new_cache, aux


# ======================================================== zamba2 hybrid
def zamba_layer_specs(cfg: ArchConfig) -> dict:
    """One Mamba2 layer: RMSNorm, mixer, residual."""
    return {"mamba": mamba_specs(cfg), "norm": rms_norm_spec(cfg.d_model)}


def zamba_shared_specs(cfg: ArchConfig) -> dict:
    """One shared transformer block (zamba2's ``Zamba2AttentionDecoderLayer``):
    RMSNorm of its input ([stream ‖ embedding] with ``concat_embed``),
    attention, RMSNorm, gated MLP.  Its uses share these weights."""
    return {
        "input_norm": Spec((cfg.attn_in_dim,), ("embed",), init="ones"),
        "attn": gqa_specs(cfg),
        "ff_norm": rms_norm_spec(cfg.d_model),
        "mlp": mlp_specs(cfg.d_model, cfg.d_ff),
    }


def zamba_use_specs(cfg: ArchConfig) -> dict:
    """What each use of a shared block has of its own: the LoRA on the
    MLP's gate and up projections, and the ``linear`` that maps the
    block's output onto the next Mamba2 layer's input."""
    d = cfg.d_model
    s = {"linear": Spec((d, d), ("embed", None))}
    if cfg.adapter_rank:
        s["adapter"] = {
            "down": Spec((d, cfg.adapter_rank), ("embed", None)),
            "up": Spec((cfg.adapter_rank, 2 * cfg.d_ff), (None, "mlp")),
        }
    return s


def zamba_layer_apply(p, x, add, cfg: ArchConfig, cache=None, layer=None, mesh=None):
    """x + Mamba2(RMSNorm(x + add)): ``add`` is a shared block's output
    through its use's ``linear`` (zeros where no block feeds this layer)."""
    with jax.named_scope("mamba"):
        y, cache = mamba_apply(p["mamba"], rms_norm(p["norm"], x + add, cfg.norm_eps), cfg,
                               cache=cache, layer=layer, mesh=mesh)
    return x + y, cache


def zamba_shared_apply(p, use, x, emb, cfg: ArchConfig, positions, *, cache=None,
                       cache_len=None, use_idx=None, mesh=None):
    """One use of a shared block: its output through the use's ``linear``,
    to be added to the next Mamba2 layer's input (the block has no
    residual of its own).  ``cache`` is the KV stack of the uses, row
    ``use_idx`` this use's."""
    with jax.named_scope("shared_block"):
        h = jnp.concatenate([x, emb], axis=-1) if cfg.concat_embed else x
        h = rms_norm(p["input_norm"], h, cfg.norm_eps)
        with jax.named_scope("attn"):
            a, cache = gqa_apply(p["attn"], h, cfg, positions, cache=cache,
                                 cache_len=cache_len, layer=use_idx, mesh=mesh)
        h = rms_norm(p["ff_norm"], a, cfg.norm_eps)
        with jax.named_scope("mlp"):
            m = gated_mlp_apply(p["mlp"], h, use.get("adapter"))
    with jax.named_scope("hybrid_linear"):
        out = m @ use["linear"]
    return out, cache


def gated_mlp_apply(p, x, adapter):
    """gelu(x·gate) · (x·up), then down (zamba2's ``hidden_act`` "gelu",
    the exact form); ``adapter`` (a LoRA, or None) adds (x·A)·B to gate
    and up, its first half to gate."""
    g, u = x @ p["gate"], x @ p["up"]
    if adapter is not None:
        # one product a half: the LoRA's whole 2·d_ff output would be a
        # temporary twice the MLP's width
        xa, ff = x @ adapter["down"], g.shape[-1]
        g = g + xa @ adapter["up"][:, :ff]
        u = u + xa @ adapter["up"][:, ff:]
    return (jax.nn.gelu(g, approximate=False) * u) @ p["down"]


# ========================================================== xLSTM groups
def xlstm_group_specs(cfg: ArchConfig) -> dict:
    from repro.models.spec import stack_specs

    k = cfg.slstm_every
    return {
        "mlstm": stack_specs(mlstm_specs(cfg), k - 1, "sublayers"),
        "slstm": slstm_specs(cfg),
    }


def xlstm_group_apply(p, x, cfg: ArchConfig, cache: Optional[dict] = None):
    """(k-1) mLSTM layers then 1 sLSTM layer; scanned as one group."""
    k = cfg.slstm_every

    def body(carry, inp):
        x, = carry
        pi, ci = inp
        y, new_ci = mlstm_apply(pi, x, cfg, cache=ci)
        return (y,), new_ci

    m_cache = cache["mlstm"] if cache is not None else None
    (x,), new_m = jax.lax.scan(
        body, (x,), (p["mlstm"], m_cache)
    )
    s_cache = cache["slstm"] if cache is not None else None
    x, new_s = slstm_apply(p["slstm"], x, cfg, cache=s_cache)
    new_cache = (
        {"mlstm": new_m, "slstm": new_s} if cache is not None else None
    )
    return x, new_cache
