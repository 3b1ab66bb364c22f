"""DeepSeek-V2-style MoE: 2 shared experts (dense TP MLP) + E routed
experts, top-k softmax gating.

Routed dispatch paths:

``moe_capacity_apply`` — mesh-free sort+gather dispatch into an (E, C, D)
    capacity buffer, expert FFNs as one *grouped GEMM* (E batched) — this is
    exactly GOLDYLOC's concurrent-GEMM pool, executed through
    ``kernels.grouped_gemm`` on TPU with the GO tile for CD=#experts.

``moe_ep_apply`` — expert-parallel shard_map: tokens (batch+seq sharded)
    route via fixed-capacity ``lax.all_to_all`` over the 'model' axis to the
    expert-owning devices, compute locally (again a grouped GEMM), and
    return.  This is the production path the multi-pod dry-run lowers.

Both are differentiable; over-capacity copies are dropped (factor-2 default,
tests use large factors and cross-check against a dense reference).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels.dispatch import use_pallas
from repro.kernels.grouped_gemm import grouped_gemm
from repro.models.common import mlp_apply, mlp_specs
from repro.models.spec import Spec


def moe_specs(cfg: ArchConfig) -> dict:
    E, d, ff = cfg.n_routed_experts, cfg.d_model, cfg.moe_d_ff
    s = {
        "router": Spec((d, E), ("embed", None)),
        "wg": Spec((E, d, ff), ("experts", "embed", None)),
        "wu": Spec((E, d, ff), ("experts", "embed", None)),
        "wd": Spec((E, ff, d), ("experts", None, "embed"), scale=0.5),
    }
    if cfg.n_shared_experts:
        s["shared"] = mlp_specs(d, cfg.n_shared_experts * cfg.moe_d_ff)
    return s


def _route(p, xt, cfg):
    """softmax gating + top-k (renormalized)."""
    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.moe_top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss.
    E = cfg.n_routed_experts
    me = probs.mean(0)
    one_hot = jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32)
    ce = one_hot.mean(0)
    aux = E * jnp.sum(me * ce)
    return w, ids, aux


def _expert_ffn(p, xbuf, interpret: Optional[bool]):
    """(E, C, D) -> (E, C, D) SwiGLU through grouped GEMMs."""
    if use_pallas() or (interpret is not None and interpret):
        from repro.core.library import default_library
        from repro.core.gemm_desc import GemmDesc

        E, C, D = xbuf.shape
        ff = p["wg"].shape[-1]
        dt = "f32" if xbuf.dtype == jnp.float32 else "bf16"
        lib = default_library()
        cd = min(16, E)
        t_up = lib.tile(GemmDesc(C, ff, D, dtype=dt), cd)
        t_dn = lib.tile(GemmDesc(C, D, ff, dtype=dt), cd)
        g = grouped_gemm(xbuf, p["wg"].astype(xbuf.dtype), tile=t_up,
                         interpret=interpret)
        u = grouped_gemm(xbuf, p["wu"].astype(xbuf.dtype), tile=t_up,
                         interpret=interpret)
        h = jax.nn.silu(g) * u
        return grouped_gemm(h, p["wd"].astype(xbuf.dtype), tile=t_dn,
                            interpret=interpret)
    g = jnp.einsum("ecd,edf->ecf", xbuf, p["wg"].astype(xbuf.dtype))
    u = jnp.einsum("ecd,edf->ecf", xbuf, p["wu"].astype(xbuf.dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(xbuf.dtype))


def _capacity_dispatch(ids_f, n_groups: int, cap: int):
    """Sort copies by group; return (slot per copy, validity)."""
    n = ids_f.shape[0]
    order = jnp.argsort(ids_f, stable=True)
    ids_s = ids_f[order]
    counts = jnp.zeros((n_groups,), jnp.int32).at[ids_f].add(1, mode="drop")
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(n, dtype=jnp.int32) - offsets[ids_s]
    valid = pos < cap
    slot_s = jnp.where(valid, ids_s * cap + pos, n_groups * cap)  # drop slot
    # un-sort back to copy order
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(n))
    return slot_s[inv], valid[inv]


def moe_capacity_apply(
    p, x, cfg: ArchConfig, *, capacity_factor: float = 2.0,
    interpret: Optional[bool] = None,
):
    """Mesh-free routed path. x (B,T,D) -> (y, aux_loss)."""
    B, T, D = x.shape
    E, k = cfg.n_routed_experts, cfg.moe_top_k
    n = B * T
    xt = x.reshape(n, D)
    w, ids, aux = _route(p, xt, cfg)

    C = max(int(math.ceil(n * k / E * capacity_factor)), 1)
    ids_f = ids.reshape(-1)
    tok_f = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    slot, valid = _capacity_dispatch(ids_f, E, C)

    table = jnp.zeros((E * C,), jnp.int32).at[slot].set(tok_f, mode="drop")
    filled = jnp.zeros((E * C,), bool).at[slot].set(valid, mode="drop")
    xbuf = jnp.where(filled[:, None], xt[table], 0.0).reshape(E, C, D)

    out = _expert_ffn(p, xbuf, interpret).reshape(E * C, D)
    copy_out = jnp.where(
        valid[:, None], out[jnp.minimum(slot, E * C - 1)], 0.0
    )
    y = jax.ops.segment_sum(
        copy_out * w.reshape(-1)[:, None].astype(copy_out.dtype), tok_f, n
    )
    y = y.reshape(B, T, D).astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], x)
    return y, aux


# ------------------------------------------------------------ EP shard_map
def moe_ep_apply(
    p, x, cfg: ArchConfig, mesh, *, capacity_factor: float = 1.25,
    data_axes=("data",), model_axis: str = "model",
):
    """Expert-parallel routed path (production): a2a dispatch over
    ``model_axis``.  x (B,T,D); experts sharded over model axis."""
    ep = mesh.shape[model_axis]
    E = cfg.n_routed_experts
    assert E % ep == 0, (E, ep)

    routed = functools.partial(
        _moe_ep_local, cfg=cfg, ep=ep, capacity_factor=capacity_factor,
        model_axis=model_axis, all_axes=tuple(mesh.axis_names),
    )
    routed_params = {k: p[k] for k in ("router", "wg", "wu", "wd")}
    pspec_w = {
        "router": P(),
        "wg": P(model_axis, None, None),
        "wu": P(model_axis, None, None),
        "wd": P(model_axis, None, None),
    }
    x_spec = P(data_axes, model_axis, None)  # tokens seq-sharded for dispatch
    y, aux = jax.shard_map(
        routed,
        mesh=mesh,
        in_specs=(pspec_w, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(routed_params, x)
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], x)
    return y, aux


def _moe_ep_local(p, x_loc, *, cfg, ep, capacity_factor, model_axis, all_axes):
    """Per-device body: route, a2a to expert owners, grouped-GEMM, a2a back."""
    Bl, Tl, D = x_loc.shape
    n = Bl * Tl
    E, k = cfg.n_routed_experts, cfg.moe_top_k
    e_loc = E // ep
    xt = x_loc.reshape(n, D)
    w, ids, aux = _route(p, xt, cfg)
    aux = jax.lax.pmean(aux, all_axes)

    # ---- send side: copies → destination devices (fixed capacity) -------
    cap = max(int(math.ceil(n * k / ep * capacity_factor)), 8)
    ids_f = ids.reshape(-1)
    tok_f = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    dst_f = ids_f // e_loc
    slot, valid = _capacity_dispatch(dst_f, ep, cap)

    wire_dt = jnp.bfloat16 if xt.dtype != jnp.float64 else xt.dtype
    xt = xt.astype(wire_dt)  # a2a payloads cross ICI in bf16 (§Perf MoE M2)
    send_x = (
        jnp.zeros((ep * cap, D), xt.dtype)
        .at[slot].set(jnp.where(valid[:, None], xt[tok_f], 0.0), mode="drop")
    )
    send_eid = (
        jnp.full((ep * cap,), e_loc, jnp.int32)  # sentinel = invalid
        .at[slot].set(jnp.where(valid, ids_f % e_loc, e_loc), mode="drop")
    )
    recv_x = jax.lax.all_to_all(
        send_x.reshape(ep, cap, D), model_axis, split_axis=0, concat_axis=0,
        tiled=False,
    ).reshape(ep * cap, D)
    recv_eid = jax.lax.all_to_all(
        send_eid.reshape(ep, cap), model_axis, split_axis=0, concat_axis=0,
        tiled=False,
    ).reshape(ep * cap)

    # ---- local expert compute (grouped GEMM over e_loc experts) ---------
    C2 = max(int(math.ceil(ep * cap / e_loc * 1.5)), 8)
    slot2, valid2 = _capacity_dispatch(recv_eid, e_loc, C2)  # sentinel drops
    valid2 &= recv_eid < e_loc
    table2 = jnp.zeros((e_loc * C2,), jnp.int32).at[slot2].set(
        jnp.arange(ep * cap, dtype=jnp.int32), mode="drop"
    )
    filled2 = jnp.zeros((e_loc * C2,), bool).at[slot2].set(valid2, mode="drop")
    xbuf = jnp.where(filled2[:, None], recv_x[table2], 0.0).reshape(
        e_loc, C2, D
    )
    out = _expert_ffn(p, xbuf, None).reshape(e_loc * C2, D)
    back = jnp.where(
        valid2[:, None], out[jnp.minimum(slot2, e_loc * C2 - 1)], 0.0
    )

    # ---- return a2a + combine at source ---------------------------------
    ret = jax.lax.all_to_all(
        back.reshape(ep, cap, D), model_axis, split_axis=0, concat_axis=0,
        tiled=False,
    ).reshape(ep * cap, D)
    copy_out = jnp.where(
        valid[:, None], ret[jnp.minimum(slot, ep * cap - 1)], 0.0
    )
    y = jax.ops.segment_sum(
        copy_out * w.reshape(-1)[:, None].astype(copy_out.dtype), tok_f, n
    )
    return y.reshape(Bl, Tl, D).astype(x_loc.dtype), aux
