"""Route model decode-step GEMMs through the serving runtime — DESIGN.md §10.5.

Serving is where the paper's scenario actually happens: each decode step
of each live request issues a bundle of small-M GEMMs (QKV / attention-out
/ FFN, or per-expert FFNs for MoE), and how many of them are pending at
once depends on traffic — exactly the "available parallelism only known at
runtime" setting of §4.4.

`decode_step_requests` enumerates one representative layer's decode-step
GEMMs for an `ArchConfig` (M = live batch), applying the §6.11
fusion-vs-concurrency policy first: shared-input projections (QKV; FFN
gate+up) are submitted as one wide fused GEMM when the cost model prefers
fusion, and as separate concurrent GEMMs when it prefers grouping.  The
jitted model still does the tensor math; the runtime is the dispatch-layer
shadow that plans, groups, and meters those same GEMMs (telemetry: CD,
mode, plan-cache hit rate), and executes them for real when
``RuntimeConfig.execute`` is set.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import replace
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.gemm_desc import GemmDesc
from repro.core.op_desc import AttentionDesc, GroupedGemmDesc, ScanDesc
from repro.core.scheduler import ConcurrencyController, GemmRequest
from repro.runtime.graph import OpGraph, out_shape, slot_shape
from repro.runtime.runtime import Runtime, Ticket


def _shared_input_requests(
    ctrl: ConcurrencyController,
    descs: Sequence[GemmDesc],
    tag: str,
) -> List[GemmRequest]:
    """Apply §6.11 to a shared-input bundle: one fused request or N grouped."""
    if len(descs) < 2:
        return [GemmRequest(desc=d, tag=tag) for d in descs]
    choice, _, _ = ctrl.plan_shared_input(list(descs))
    if choice == "fuse":
        fused = replace(descs[0], N=sum(d.N for d in descs))
        return [GemmRequest(desc=fused, tag=f"{tag}-fused")]
    return [GemmRequest(desc=d, tag=tag) for d in descs]


def decode_step_descs(cfg, batch: int, dtype: str = "bf16") -> List[Tuple[str, List[GemmDesc]]]:
    """(tag, shared-input bundle) pairs for one decode step of one layer.

    Bundles listed together share their A operand (the hidden state), so
    they are §6.11 fusion candidates; distinct bundles are only groupable
    via §6.7 compatibility classes."""
    M, D = batch, cfg.d_model
    hd = cfg.resolved_head_dim
    out: List[Tuple[str, List[GemmDesc]]] = []

    if cfg.family == "hybrid":
        return _hybrid_descs(cfg, M, dtype)
    if cfg.attn_type == "mla":
        # MLA (DeepSeek-V2): low-rank KV/Q down-projections + up-projection.
        q_n = cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        kv_n = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        if cfg.q_lora_rank:
            out.append(("mla-down", [GemmDesc(M, cfg.q_lora_rank, D, dtype=dtype),
                                     GemmDesc(M, kv_n, D, dtype=dtype)]))
            out.append(("mla-q-up", [GemmDesc(M, q_n, cfg.q_lora_rank, dtype=dtype)]))
        else:
            out.append(("mla-down", [GemmDesc(M, q_n, D, dtype=dtype),
                                     GemmDesc(M, kv_n, D, dtype=dtype)]))
        out.append(("attn-out", [GemmDesc(M, D, cfg.n_heads * cfg.v_head_dim,
                                          dtype=dtype)]))
    elif cfg.family == "ssm":
        # Mamba2-style block: wide in-projection + out-projection.
        out.append(("ssm-in", [GemmDesc(M, 2 * cfg.ssm_d_inner, D, dtype=dtype)]))
        out.append(("ssm-out", [GemmDesc(M, D, cfg.ssm_d_inner, dtype=dtype)]))
    else:
        # GQA attention: Q + K + V share the hidden state (§6.11 QKV case).
        out.append(("qkv", [GemmDesc(M, cfg.n_heads * hd, D, dtype=dtype),
                            GemmDesc(M, cfg.n_kv_heads * hd, D, dtype=dtype),
                            GemmDesc(M, cfg.n_kv_heads * hd, D, dtype=dtype)]))
        out.append(("attn-out", [GemmDesc(M, D, cfg.n_heads * hd, dtype=dtype)]))

    if cfg.n_routed_experts:
        # Active routed experts are genuinely independent GEMMs — the §6.7
        # concurrency pool.  gate+up share the expert input (§6.11).
        ff = cfg.moe_d_ff
        for e in range(cfg.moe_top_k):
            out.append((f"expert{e}-up", [GemmDesc(M, ff, D, dtype=dtype),
                                          GemmDesc(M, ff, D, dtype=dtype)]))
            out.append((f"expert{e}-down", [GemmDesc(M, D, ff, dtype=dtype)]))
        if cfg.n_shared_experts:
            # the model implements shared experts as ONE dense MLP of width
            # n_shared * moe_d_ff (models/moe.py:moe_specs) — mirror that
            sff = cfg.n_shared_experts * ff
            out.append(("shared-up", [GemmDesc(M, sff, D, dtype=dtype),
                                      GemmDesc(M, sff, D, dtype=dtype)]))
            out.append(("shared-down", [GemmDesc(M, D, sff, dtype=dtype)]))
    elif cfg.d_ff > 0:  # xLSTM-style blocks have no separate FFN
        ff = cfg.d_ff
        out.append(("ffn-up", [GemmDesc(M, ff, D, dtype=dtype),
                               GemmDesc(M, ff, D, dtype=dtype)]))
        out.append(("ffn-down", [GemmDesc(M, D, ff, dtype=dtype)]))
    return out


def _hybrid_descs(cfg, M: int, dtype: str) -> List[Tuple[str, List[GemmDesc]]]:
    """One zamba2 hybrid layer in its published series order and widths:
    the shared block's q/k/v over its input ([stream ‖ embedding] with
    ``concat_embed``) and its output projection, the gated MLP with the
    use's LoRA (down to the rank, up to gate and up), the use's
    ``linear``, then the Mamba2 layer's in- and out-projections."""
    D, hd, ff = cfg.d_model, cfg.resolved_head_dim, cfg.d_ff
    din = cfg.attn_in_dim
    di, H = cfg.ssm_d_inner, cfg.ssm_n_heads
    out = [("qkv", [GemmDesc(M, cfg.n_heads * hd, din, dtype=dtype),
                    GemmDesc(M, cfg.n_kv_heads * hd, din, dtype=dtype),
                    GemmDesc(M, cfg.n_kv_heads * hd, din, dtype=dtype)]),
           ("attn-out", [GemmDesc(M, D, cfg.n_heads * hd, dtype=dtype)]),
           ("ffn-up", [GemmDesc(M, ff, D, dtype=dtype), GemmDesc(M, ff, D, dtype=dtype)])]
    if cfg.adapter_rank:
        out += [("adapter-down", [GemmDesc(M, cfg.adapter_rank, D, dtype=dtype)]),
                ("adapter-up", [GemmDesc(M, 2 * ff, cfg.adapter_rank, dtype=dtype)])]
    return out + [("ffn-down", [GemmDesc(M, D, ff, dtype=dtype)]),
                  ("linear", [GemmDesc(M, D, D, dtype=dtype)]),
                  ("ssm-in", [GemmDesc(M, di + cfg.ssm_conv_channels + H, D, dtype=dtype)]),
                  ("ssm-out", [GemmDesc(M, D, di, dtype=dtype)])]


def decode_step_requests(
    ctrl: ConcurrencyController,
    cfg,
    batch: int,
    dtype: str = "bf16",
    fuse_policy: bool = True,
) -> List[GemmRequest]:
    """One decode step's GEMM requests.

    ``fuse_policy=True`` applies §6.11 to each shared-input bundle (the
    GOLDYLOC path); ``False`` emits the raw unfused GEMM stream — what a
    framework dispatches by default, i.e. the baseline workload."""
    reqs: List[GemmRequest] = []
    for tag, bundle in decode_step_descs(cfg, batch, dtype):
        if fuse_policy:
            reqs += _shared_input_requests(ctrl, bundle, tag)
        else:
            reqs += [GemmRequest(desc=d, tag=tag) for d in bundle]
    return reqs


def decode_step_op_descs(
    cfg, batch: int, context: int = 1024, dtype: str = "bf16",
) -> List[object]:
    """The FULL decode-step op bundle for one layer of an `ArchConfig` —
    every kernel family the step actually launches, not just its GEMMs
    (DESIGN.md §14):

    - the projection/FFN GEMMs of `decode_step_descs`;
    - the attention read over ``context`` cached tokens
      (`AttentionDesc`, Sq = 1 per sequence);
    - the routed-expert pool as ONE ragged grouped-GEMM launch per
      up/down projection (`GroupedGemmDesc`) — this is the §6.7
      concurrency pool collapsed into the kernel that actually runs it;
    - the SSD state update for SSM/hybrid blocks (`ScanDesc`, T = 1).

    This is the heterogeneous pool `Runtime.submit_bundle` co-schedules.
    """
    descs: List[object] = [
        d for _, bundle in decode_step_descs(cfg, batch, dtype)
        for d in bundle
    ]
    if cfg.attn_type == "mla":
        hd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        descs.append(AttentionDesc(batch, cfg.n_heads, cfg.n_heads, 1,
                                   context, hd, True, dtype))
    elif not (cfg.family == "ssm"):
        hd = cfg.resolved_head_dim
        descs.append(AttentionDesc(batch, cfg.n_heads, cfg.n_kv_heads, 1,
                                   context, hd, True, dtype))
    if cfg.family in ("ssm", "hybrid") and cfg.ssm_state:
        descs.append(ScanDesc(batch, 1, cfg.ssm_n_heads, cfg.ssm_head_dim,
                              cfg.ssm_state, dtype))
    elif cfg.family == "ssm":
        # xLSTM-style blocks (ssm_state == 0): each mLSTM layer runs two
        # SSD scans per step — the (N = P = 2D/H) C-matrix recurrence and
        # the P = 1 normalizer (models/xlstm.py:mlstm_apply).
        hp = 2 * cfg.d_model // cfg.n_heads
        descs.append(ScanDesc(batch, 1, cfg.n_heads, hp, hp, dtype))
        descs.append(ScanDesc(batch, 1, cfg.n_heads, 1, hp, dtype))
    if cfg.n_routed_experts:
        # The routed experts as the ragged pool the MoE layer dispatches:
        # batch·top_k rows spread over the active experts.
        g = min(cfg.n_routed_experts, max(batch * cfg.moe_top_k, 1))
        rows = batch * cfg.moe_top_k
        descs.append(GroupedGemmDesc(g, rows, cfg.moe_d_ff, cfg.d_model,
                                     dtype))
        descs.append(GroupedGemmDesc(g, rows, cfg.d_model, cfg.moe_d_ff,
                                     dtype))
    return descs


def _wire(
    graph: OpGraph,
    name: str,
    desc,
    feeds: Optional[Dict[object, Optional[str]]] = None,
    after: Sequence[str] = (),
    tag: str = "",
) -> str:
    """Add a node whose candidate producers become DATA edges when the
    element counts line up and CONTROL edges when they don't (§19.1).

    A decode step's real dataflow passes through state and glue the
    runtime does not model as ops — the KV cache, expert routing
    scatter, residual adds, norms.  Where the producer's output is
    shape-compatible with the consumer's operand slot the edge carries
    the tensor (and executes for real when operands are present); where
    it is not (attention k/v read the *cache*, not this step's k/v
    projection), the dependency is ordering-only.  One helper, one
    policy, every architecture."""
    deps: Dict[object, str] = {}
    ctrl = list(after)
    for slot, src in (feeds or {}).items():
        if src is None:
            continue
        if (math.prod(out_shape(graph.nodes[src].desc))
                == math.prod(slot_shape(desc, slot))):
            deps[slot] = src
        else:
            ctrl.append(src)
    return graph.add(name, desc, deps=deps, after=ctrl, tag=tag)


def decode_step_graph(
    cfg,
    batch: int,
    context: int = 1024,
    dtype: str = "bf16",
    layers: int = 1,
) -> OpGraph:
    """The dependency graph of ``layers`` decode-step layers (§19.2) —
    the same op population as `decode_step_op_descs`, with the chain
    structure the flat bundle erases:

    - GQA: q/k/v projections → attention (q feeds the query slot; k/v
      are control edges, the cache carries the data) → O-projection →
      gate/up → down;
    - MLA: q/kv down-projections → q up-projection → attention →
      O-projection (control: v_head_dim ≠ qk head dim) → MoE;
    - MoE: the routed pool as its two ragged grouped-GEMM launches
      (routing scatter = control edge in, up→down = data edge) plus the
      shared-expert dense MLP, all fed by the attention output;
    - SSM: in-projection → SSD scan → out-projection;
    - hybrid (zamba2), in series as the published layer runs: the shared
      block's q/k/v → attention → O-projection → gate/up and the LoRA →
      down → the use's linear → the Mamba2 in-projection → SSD scan →
      out-projection.

    Consecutive layers chain by control edges from layer sinks to the
    next layer's input projections.  Per-layer node names are prefixed
    ``L<i>.`` (e.g. ``"L0.attn"``); the single-layer names are the bare
    suffixes users see in telemetry tags.

    What a caller could express before this existed: `waves()` of this
    graph, one barrier'd bundle per wave — exactly the baseline
    `benchmarks/serving.py run_graph` compares against.
    """
    g = OpGraph()
    sinks: List[str] = []
    for ell in range(layers):
        sinks = _add_decode_layer(g, cfg, batch, context, dtype,
                                  prefix=f"L{ell}." if layers > 1 else "",
                                  roots_after=sinks)
    g.validate()
    return g


def _add_decode_layer(
    g: OpGraph, cfg, batch: int, context: int, dtype: str,
    prefix: str, roots_after: List[str],
) -> List[str]:
    """Wire one layer; returns its sink node names (the next layer's
    control-edge sources)."""
    bundles = dict(decode_step_descs(cfg, batch, dtype))
    P = prefix
    if cfg.family == "hybrid":
        return _add_hybrid_layer(g, cfg, bundles, batch, context, dtype, P, roots_after)
    sinks: List[str] = []

    # ------------------------------------------------ attention / SSM
    if cfg.attn_type == "mla":
        down = bundles["mla-down"]
        q_src = _wire(g, P + "q-down", down[0], after=roots_after,
                      tag="mla-down")
        kv = _wire(g, P + "kv-down", down[1], after=roots_after,
                   tag="mla-down")
        if "mla-q-up" in bundles:
            q_src = _wire(g, P + "q-up", bundles["mla-q-up"][0],
                          feeds={"a": q_src}, tag="mla-q-up")
        hd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        attn = _wire(g, P + "attn",
                     AttentionDesc(batch, cfg.n_heads, cfg.n_heads, 1,
                                   context, hd, True, dtype),
                     feeds={0: q_src}, after=[kv], tag="attn")
        block_out = _wire(g, P + "o", bundles["attn-out"][0],
                          feeds={"a": attn}, tag="attn-out")
    elif "ssm-in" in bundles:
        ssm_in = _wire(g, P + "ssm-in", bundles["ssm-in"][0],
                       after=roots_after, tag="ssm-in")
        if cfg.family == "ssm" and not cfg.ssm_state:
            # xLSTM mLSTM step: C-matrix recurrence + normalizer scan.
            hp = 2 * cfg.d_model // cfg.n_heads
            scan = _wire(g, P + "scan",
                         ScanDesc(batch, 1, cfg.n_heads, hp, hp, dtype),
                         feeds={0: ssm_in}, tag="scan")
            norm = _wire(g, P + "scan-norm",
                         ScanDesc(batch, 1, cfg.n_heads, 1, hp, dtype),
                         feeds={0: ssm_in}, tag="scan")
            block_out = _wire(g, P + "ssm-out", bundles["ssm-out"][0],
                              feeds={"a": scan}, after=[norm],
                              tag="ssm-out")
        else:
            scan = _wire(g, P + "scan",
                         ScanDesc(batch, 1, cfg.ssm_n_heads,
                                  cfg.ssm_head_dim, cfg.ssm_state, dtype),
                         feeds={0: ssm_in}, tag="scan")
            block_out = _wire(g, P + "ssm-out", bundles["ssm-out"][0],
                              feeds={"a": scan}, tag="ssm-out")
    else:
        qkv = bundles["qkv"]
        hd = cfg.resolved_head_dim
        q = _wire(g, P + "q", qkv[0], after=roots_after, tag="qkv")
        k = _wire(g, P + "k", qkv[1], after=roots_after, tag="qkv")
        v = _wire(g, P + "v", qkv[2], after=roots_after, tag="qkv")
        attn = _wire(g, P + "attn",
                     AttentionDesc(batch, cfg.n_heads, cfg.n_kv_heads, 1,
                                   context, hd, True, dtype),
                     feeds={0: q}, after=[k, v], tag="attn")
        block_out = _wire(g, P + "o", bundles["attn-out"][0],
                          feeds={"a": attn}, tag="attn-out")

    # --------------------------------------------------------- FFN / MoE
    if cfg.n_routed_experts:
        # The routed pool as the ragged launches that actually run it
        # (`decode_step_op_descs`); the per-expert dense GEMMs are that
        # same work pre-collapse, so the graph carries only the grouped
        # form.  Routing scatter in = control edge; up → down = data.
        ga = min(cfg.n_routed_experts, max(batch * cfg.moe_top_k, 1))
        rows = batch * cfg.moe_top_k
        up = _wire(g, P + "moe-up",
                   GroupedGemmDesc(ga, rows, cfg.moe_d_ff, cfg.d_model,
                                   dtype),
                   feeds={0: block_out}, tag="moe-up")
        sinks.append(_wire(g, P + "moe-down",
                           GroupedGemmDesc(ga, rows, cfg.d_model,
                                           cfg.moe_d_ff, dtype),
                           feeds={0: up}, tag="moe-down"))
        if cfg.n_shared_experts:
            sg = _wire(g, P + "shared-gate", bundles["shared-up"][0],
                       feeds={"a": block_out}, tag="shared-up")
            su = _wire(g, P + "shared-up", bundles["shared-up"][1],
                       feeds={"a": block_out}, tag="shared-up")
            sinks.append(_wire(g, P + "shared-down",
                               bundles["shared-down"][0],
                               feeds={"a": su}, after=[sg],
                               tag="shared-down"))
    elif cfg.d_ff > 0:
        gate = _wire(g, P + "gate", bundles["ffn-up"][0],
                     feeds={"a": block_out}, tag="ffn-up")
        up = _wire(g, P + "up", bundles["ffn-up"][1],
                   feeds={"a": block_out}, tag="ffn-up")
        sinks.append(_wire(g, P + "down", bundles["ffn-down"][0],
                           feeds={"a": up}, after=[gate], tag="ffn-down"))
    else:
        sinks.append(block_out)
    return sinks


def _add_hybrid_layer(g: OpGraph, cfg, b, batch: int, context: int, dtype: str,
                      P: str, roots_after: List[str]) -> List[str]:
    """Wire one zamba2 hybrid layer in series (`_hybrid_descs`' order)."""
    q = _wire(g, P + "q", b["qkv"][0], after=roots_after, tag="qkv")
    k = _wire(g, P + "k", b["qkv"][1], after=roots_after, tag="qkv")
    v = _wire(g, P + "v", b["qkv"][2], after=roots_after, tag="qkv")
    attn = _wire(g, P + "attn",
                 AttentionDesc(batch, cfg.n_heads, cfg.n_kv_heads, 1, context,
                               cfg.resolved_head_dim, True, dtype),
                 feeds={0: q}, after=[k, v], tag="attn")
    o = _wire(g, P + "o", b["attn-out"][0], feeds={"a": attn}, tag="attn-out")
    gate = _wire(g, P + "gate", b["ffn-up"][0], feeds={"a": o}, tag="ffn-up")
    up = _wire(g, P + "up", b["ffn-up"][1], feeds={"a": o}, tag="ffn-up")
    before_down = [gate]
    if "adapter-down" in b:
        lora = _wire(g, P + "adapter-down", b["adapter-down"][0], feeds={"a": o},
                     tag="adapter-down")
        before_down.append(_wire(g, P + "adapter-up", b["adapter-up"][0],
                                 feeds={"a": lora}, tag="adapter-up"))
    down = _wire(g, P + "down", b["ffn-down"][0], feeds={"a": up}, after=before_down,
                 tag="ffn-down")
    linear = _wire(g, P + "linear", b["linear"][0], feeds={"a": down}, tag="linear")
    ssm_in = _wire(g, P + "ssm-in", b["ssm-in"][0], feeds={"a": linear}, tag="ssm-in")
    scan = _wire(g, P + "scan",
                 ScanDesc(batch, 1, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state, dtype),
                 feeds={0: ssm_in}, tag="scan")
    return [_wire(g, P + "ssm-out", b["ssm-out"][0], feeds={"a": scan}, tag="ssm-out")]


def submit_decode_graph(
    runtime: Runtime,
    cfg,
    batch: int,
    context: int = 1024,
    layers: int = 1,
    tenant: str = "default",
    now: float | None = None,
    dtype: str = "bf16",
) -> Ticket:
    """Admit one request's decode step as a dependency graph (§19.2):
    returns the single graph handle; per-node results are addressable by
    the `decode_step_graph` node names."""
    return runtime.submit(
        decode_step_graph(cfg, batch, context, dtype, layers),
        tenant=tenant, now=now)


def submit_decode_bundle(
    runtime: Runtime,
    cfg,
    batch: int,
    context: int = 1024,
    tenant: str = "default",
    now: float | None = None,
    dtype: str = "bf16",
) -> List[Ticket]:
    """Deprecated: use ``runtime.submit(decode_step_op_descs(...))`` for
    the flat bundle or `submit_decode_graph` for the dataflow form
    (§19)."""
    warnings.warn(
        "integration.submit_decode_bundle is deprecated; use "
        "runtime.submit(decode_step_op_descs(...)) or submit_decode_graph "
        "(DESIGN.md §19)",
        DeprecationWarning, stacklevel=2)
    return list(runtime.submit(
        decode_step_op_descs(cfg, batch, context, dtype),
        tenant=tenant, now=now,
    ).members)


def prewarm_decode(
    runtime: Runtime, cfg, batches: Sequence[int], dtype: str = "bf16"
) -> int:
    """Tune every GEMM a decode workload can issue before traffic arrives."""
    descs: List[GemmDesc] = []
    for b in batches:
        for r in decode_step_requests(runtime.ctrl, cfg, b, dtype):
            descs.append(r.desc)
    return runtime.prewarm(descs)


def submit_decode_step(
    runtime: Runtime,
    cfg,
    batch: int,
    tenant: str = "default",
    now: float | None = None,
    dtype: str = "bf16",
) -> List[Ticket]:
    """Admit one decode step's GEMMs into the runtime queues."""
    return [
        runtime.submit(r, tenant=tenant, now=now)
        for r in decode_step_requests(runtime.ctrl, cfg, batch, dtype)
    ]
