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
from repro.models.ssm import (
    MambaCache,
    init_mamba_cache,
    mamba_apply,
    mamba_specs,
)
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
    return {"mamba": mamba_specs(cfg), "norm": rms_norm_spec(cfg.d_model)}


def zamba_shared_specs(cfg: ArchConfig) -> dict:
    """Single weight-tied transformer block applied every ``attn_every``."""
    return attn_block_specs(cfg, cfg.d_ff, moe=False)


def zamba_layer_apply(
    p, shared_p, x, cfg: ArchConfig, positions, layer_idx,
    cache: Optional[dict] = None, cache_len=None, mesh=None,
):
    """One mamba layer; on every ``attn_every``-th layer also the shared
    attention block (weight-tied across applications)."""
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    m_cache = cache["mamba"] if cache is not None else None
    y, new_m_cache = mamba_apply(p["mamba"], h, cfg, cache=m_cache, mesh=mesh)
    x = x + y

    apply_shared = (layer_idx % cfg.attn_every) == cfg.attn_every - 1

    if cache is None:
        def with_shared_nc(x):
            return attn_block_apply(shared_p, x, cfg, positions, moe=False,
                                    mesh=mesh)[0]

        x2 = jax.lax.cond(apply_shared, with_shared_nc, lambda x: x, x)
        new_kv = None
    else:
        def with_shared(args):
            x, kv = args
            # this layer's own KV cache, as a stack of one
            y, new_kv, _ = attn_block_apply(
                shared_p, x, cfg, positions, moe=False,
                cache=jax.tree.map(lambda a: a[None], kv),
                cache_len=cache_len, layer=0, mesh=mesh,
            )
            return y, jax.tree.map(lambda a: a[0], new_kv)

        x2, new_kv = jax.lax.cond(
            apply_shared, with_shared, lambda a: a, (x, cache["kv"])
        )
    new_cache = (
        {"mamba": new_m_cache, "kv": new_kv} if cache is not None else None
    )
    return x2, new_cache


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
