"""Closed-loop online serving benchmark — the runtime's end-to-end proof.

Replays Poisson and bursty decode-step arrival traces from several model
configs (multi-tenant: one tenant per arch) through the online runtime
(`repro.runtime`, DESIGN.md §10) and two baselines, on a modeled
single-device timeline:

- **sequential** — every GEMM runs alone with its isolated-tuned kernel
  (the paper's sequential baseline);
- **static-cd4** — GEMMs group up to a fixed CD=4 with isolated-tuned
  tiles (static concurrency, no GO kernels, no dynamic logic);
- **goldyloc** — the runtime: dynamic CD on queue heads, GO tiles, §6.11
  fusion, plan cache.

Reports latency percentiles, throughput, busy-time speedup vs sequential,
and the runtime's plan-cache hit rate.  A final `--verify` pass pushes one
flush through the real pallas kernels (interpret mode on CPU) and checks
the results against the XLA reference.

``--mixed-ops`` additionally replays the heterogeneous decode bundles of
an MoE (MLA attention + routed grouped-GEMM) and a hybrid-SSM tenant
through `Runtime.submit` — the flushed pool spans all four kernel
families (gemm, grouped_gemm, flash_attention, mamba_scan) and is
co-scheduled by `plan_mixed` (DESIGN.md §14); the section reports the
modeled concurrent-vs-sequential speedup of that pool.

The **graph** section (always run; also `run_graph [--smoke]` as the CI
subcommand) compares dataflow submission (`Runtime.submit(OpGraph)`,
DESIGN.md §19) against wave-barriered bundle-per-request submission of
the identical op population: two tenants' multi-layer decode graphs
overlap (one request's attention concurrent with the other's experts),
gated ≥1.05x on modeled makespan with cross-graph mixed groups visible
in telemetry.

    PYTHONPATH=src python -m benchmarks.serving [--duration 0.5] [--rate 150]

**Regenerating results/**: this script rewrites `results/serving.csv`,
`results/BENCH_serving.json` (the count-based metrics the CI bench-trend
job gates against the committed copy), and `results/serving_golib.json`
on every run.  The GO library file records its schema version
(`repro.core.library.SCHEMA_VERSION`); v1 files (pre-split-K search
space) are discarded at load with a warning and re-tuned, while
v2/v3/v4 files are **migrated** to v5 (DESIGN.md §14–§16) — their
entries were tuned on search spaces v5 subsumes, so tiles are preserved
bitwise (v2 additionally gains ``family="gemm"``; short tile lists
default ``stream_k=0``; measured provenance defaults absent), and the
save at the end of the run rewrites the file under the compact v5
envelope (5-element tiles ``[bm, bn, bk, split_k, stream_k]``).  A
stale library is never silently used to mis-plan.

The report also carries a **measured** section (DESIGN.md §16): the GO
picks of a small decode grid timed on the interpret backend next to
their modeled times — only the finite-cell count is trend-gated.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import json  # noqa: E402

import numpy as np  # noqa: E402

from benchmarks.context import RESULTS  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import (  # noqa: E402
    FAMILIES,
    ConcurrencyController,
    GOLibrary,
    family_of,
    isolated_time,
)
from repro.core.gemm_desc import GemmDesc  # noqa: E402
from repro.core.scheduler import GemmRequest  # noqa: E402
from repro.core.op_desc import slice_plan  # noqa: E402
from repro.kernels.dispatch import interpret_mode  # noqa: E402
from repro.runtime import (  # noqa: E402
    FaultInjector,
    FaultRule,
    Runtime,
    RuntimeConfig,
    TenantSLO,
    adversarial_trace,
    bursty_trace,
    decode_step_graph,
    decode_step_op_descs,
    decode_step_requests,
    poisson_trace,
)

ARCHES = ("deepseek-v2-lite-16b", "stablelm-3b", "musicgen-medium",
          "xlstm-350m")
# Mixed-ops tenants: together their decode bundles span all four kernel
# families (MoE: gemm + MLA flash-attention + routed grouped-GEMM;
# hybrid: gemm + GQA flash-attention + SSD mamba-scan).
MIXED_ARCHES = ("deepseek-v2-lite-16b", "zamba2-1.2b")
BATCH = 8
WINDOW_S = 5e-3

Event = Tuple[float, str, List[GemmRequest]]


class FixedCDController(ConcurrencyController):
    """Static-concurrency baseline: constant CD, isolated-tuned tiles."""

    def __init__(self, cd: int, **kw):
        super().__init__(go_tiles=False, **kw)
        self.fixed_cd = cd

    def preferred_cd(self, desc: GemmDesc, available: int) -> int:
        return max(1, min(self.fixed_cd, available))


def build_arrivals(
    trace_kind: str, rate_hz: float, duration_s: float
) -> List[Tuple[float, str]]:
    """(time, tenant-arch) decode-step arrivals, merged and time-sorted."""
    arrivals: List[Tuple[float, str]] = []
    for i, arch in enumerate(ARCHES):
        if trace_kind == "poisson":
            times = poisson_trace(rate_hz, duration_s, seed=100 + i)
        else:
            times = bursty_trace(rate_hz, duration_s, seed=100 + i)
        arrivals += [(t, arch) for t in times]
    arrivals.sort(key=lambda e: e[0])
    return arrivals


def build_events(
    ctrl: ConcurrencyController,
    arrivals: List[Tuple[float, str]],
    fuse_policy: bool,
) -> List[Event]:
    """Bind each decode-step arrival to its GEMM requests under the given
    dispatch policy.  §6.11 fusion is a GOLDYLOC capability, so baselines
    replay the raw unfused GEMM stream (``fuse_policy=False``)."""
    per_arch = {
        arch: decode_step_requests(ctrl, get_arch(arch), BATCH,
                                   fuse_policy=fuse_policy)
        for arch in {a for _, a in arrivals}
    }
    return [(t, arch, per_arch[arch]) for t, arch in arrivals]


def replay(runtime: Runtime, events: List[Event]) -> Dict[str, float]:
    """Open-loop replay on a virtual clock; returns latency/throughput
    stats from the runtime's modeled device timeline."""
    # Tune ahead of traffic and seed the plan cache with the 1–5-step
    # queue signatures every tenant will produce (DESIGN.md §10.2).
    first_bundle = {}
    for _, tenant, reqs in events:
        first_bundle.setdefault(tenant, [r.desc for r in reqs])
    for descs in first_bundle.values():
        for k in range(1, 6):
            runtime.prewarm(descs * k)
    tickets = []
    for t, tenant, reqs in events:
        runtime.flush(now=t)
        for r in reqs:
            tickets.append(runtime.submit(r, tenant=tenant, now=t))
    end = events[-1][0] + WINDOW_S if events else 0.0
    runtime.drain(now=end)
    lat = np.asarray([tk.latency_s for tk in tickets], float)
    busy = runtime.telemetry.modeled_busy_time_s()
    return {
        "requests": len(tickets),
        "p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "p95_ms": float(np.percentile(lat, 95)) * 1e3,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "mean_ms": float(lat.mean()) * 1e3,
        "busy_s": busy,
        # decode steps/s: comparable across systems (fusion changes the
        # per-step GEMM count, so GEMMs/s would not be).
        "throughput_steps_per_s": len(events) / max(runtime.device_free_t, 1e-12),
        "hit_rate": runtime.telemetry.cache_hit_rate(),
        "hit_rate_steady": runtime.telemetry.steady_state_hit_rate(),
        "mean_cd": runtime.telemetry.mean_cd(),
    }


def run_trace(lib: GOLibrary, trace_kind: str, rate_hz: float,
              duration_s: float) -> Dict[str, Dict[str, float]]:
    arrivals = build_arrivals(trace_kind, rate_hz, duration_s)
    systems = {
        "sequential": (FixedCDController(1, library=lib), False),
        "static-cd4": (FixedCDController(4, library=lib), False),
        "goldyloc": (ConcurrencyController(library=lib), True),
    }
    out: Dict[str, Dict[str, float]] = {}
    for name, (ctrl, fuse) in systems.items():
        events = build_events(ctrl, arrivals, fuse_policy=fuse)
        rt = Runtime(ctrl, RuntimeConfig(window_s=WINDOW_S))
        out[name] = replay(rt, events)
    seq_busy = out["sequential"]["busy_s"]
    for name in out:
        out[name]["speedup_vs_seq"] = seq_busy / max(out[name]["busy_s"], 1e-12)
    return out


def run_mixed_ops(lib: GOLibrary, steps: int = 60) -> Dict[str, object]:
    """Heterogeneous co-scheduling section (DESIGN.md §14).

    Each virtual step, every mixed tenant submits its FULL decode op
    bundle via `Runtime.submit` (a sequence → the §14 mixed queue); one
    flush co-schedules the pooled heterogeneous ops through
    `plan_mixed`.  The sequential baseline runs every op alone with its
    isolated-tuned kernel (one launch each) — the same baseline
    semantics as the trace replay above."""
    ctrl = ConcurrencyController(library=lib)
    rt = Runtime(ctrl, RuntimeConfig(window_s=WINDOW_S))
    bundles = {a: decode_step_op_descs(get_arch(a), BATCH)
               for a in MIXED_ARCHES}
    pool = [d for b in bundles.values() for d in b]
    families = sorted({family_of(d) for d in pool})
    assert families == sorted(FAMILIES), (
        f"mixed pool must span all four kernel families, got {families}")
    for b in bundles.values():
        rt.prewarm(b)
    seq_step = sum(isolated_time(d, lib.get(d).isolated) for d in pool)
    for i in range(steps):
        t = i * (WINDOW_S * 4)
        for arch, bundle in bundles.items():
            rt.submit(bundle, tenant=arch, now=t)
        rt.flush(now=t + WINDOW_S, force=True)
    rt.drain(now=steps * WINDOW_S * 4)
    tele = rt.telemetry
    busy = tele.modeled_busy_time_s()
    out = {
        "tenants": list(MIXED_ARCHES),
        "families": families,
        "bundle_ops_per_step": len(pool),
        "steps": steps,
        "modes": tele.mode_counts(),
        "mean_cd": round(tele.mean_cd(), 3),
        "max_cd": tele.max_cd(),
        "hit_rate_steady": round(tele.steady_state_hit_rate(), 4),
        "sequential_busy_s": seq_step * steps,
        "mixed_busy_s": busy,
        "speedup_vs_sequential": (seq_step * steps) / max(busy, 1e-12),
    }
    return out


# §19 graph scenario: an MoE tenant and a hybrid-SSM tenant decode
# side by side — chain structures differ enough that one request's
# attention/scan genuinely overlaps the other's experts.  Small batch
# keeps the ops memory-bound, where grouping buys the most.
GRAPH_ARCHES = ("deepseek-v2-lite-16b", "zamba2-1.2b")
GRAPH_BATCH = 4
GRAPH_LAYERS = 2
GRAPH_REQUESTS = 2  # concurrent in-flight requests per tenant


def run_graph(lib: GOLibrary, steps: int = 6, smoke: bool = False
              ) -> Dict[str, object]:
    """Dataflow-vs-bundle submission on modeled makespan (DESIGN.md §19.4).

    Two tenants each decode ``GRAPH_REQUESTS`` concurrent
    ``GRAPH_LAYERS``-layer dependency graphs (`decode_step_graph`) for
    ``steps`` virtual steps:

    - **graph**: both requests' graphs are live at once; the readiness
      tracker feeds every concurrency window with ready nodes from
      either request, so request A's attention shares groups with
      request B's experts (`cross_graph_groups` counts them).
    - **bundle** (the pre-§19 API ceiling): the same op population
      submitted request-serially as one bundle per topological wave with
      a drain barrier after each — the caller-driven schedule the flat
      `submit(sequence)` surface forces.

    Same descriptors, same library, same prewarm (per-wave signatures,
    which favor the *baseline*: its flush signatures are exactly the
    prewarmed ones).  Gates: graph beats bundle ≥1.05x on makespan;
    ≥1 cross-graph mixed group; every graph completes and counts as ONE
    logical request (§19.3)."""
    if smoke:
        steps = 2
    graphs = {a: decode_step_graph(get_arch(a), GRAPH_BATCH,
                                   layers=GRAPH_LAYERS)
              for a in GRAPH_ARCHES}

    rt = Runtime(ConcurrencyController(library=lib),
                 RuntimeConfig(window_s=0.0))
    for g in graphs.values():
        rt.prewarm(g)
    handles = []
    for _ in range(steps):
        now = rt.device_free_t
        for arch, g in graphs.items():
            for _ in range(GRAPH_REQUESTS):
                handles.append(rt.submit(g, tenant=arch, now=now))
        rt.drain(now=now)
    graph_makespan = rt.device_free_t
    tele = rt.telemetry

    rtb = Runtime(ConcurrencyController(library=lib),
                  RuntimeConfig(window_s=0.0))
    for g in graphs.values():
        rtb.prewarm(g)
    for _ in range(steps):
        for arch, g in graphs.items():
            for _ in range(GRAPH_REQUESTS):
                for wave in g.waves():
                    rtb.submit([g.nodes[n].desc for n in wave],
                               tenant=arch, now=rtb.device_free_t)
                    rtb.drain(now=rtb.device_free_t)
    bundle_makespan = rtb.device_free_t

    lat = np.asarray([h.latency_s for h in handles], float)
    out = {
        "tenants": list(GRAPH_ARCHES),
        "layers": GRAPH_LAYERS,
        "batch": GRAPH_BATCH,
        "requests_per_tenant": GRAPH_REQUESTS,
        "steps": steps,
        "smoke": smoke,
        "nodes_per_step": sum(len(g) for g in graphs.values()),
        "graph_requests": tele.graphs_submitted,
        "graphs_completed": tele.graphs_completed,
        "graph_makespan_s": graph_makespan,
        "bundle_makespan_s": bundle_makespan,
        "graph_speedup": bundle_makespan / max(graph_makespan, 1e-12),
        "cross_graph_groups": tele.cross_graph_groups(),
        "max_ready_depth": tele.max_ready_depth,
        "ready_depths": tele.ready_depth_histogram(),
        "graph_p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "mean_cd": round(tele.mean_cd(), 3),
    }
    # ------------------------------------------------------------- gates
    assert tele.graphs_completed == tele.graphs_submitted, (
        f"{tele.graphs_submitted - tele.graphs_completed} graphs never "
        f"completed")
    assert tele.completed == tele.submitted == tele.graphs_submitted, (
        "a graph must count as exactly ONE logical request (§19.3): "
        f"submitted={tele.submitted} completed={tele.completed} "
        f"graphs={tele.graphs_submitted}")
    assert all(h.done for h in handles)
    assert out["cross_graph_groups"] >= 1, (
        "no concurrency window mixed nodes from two graphs — the "
        "dataflow executor is not overlapping requests")
    assert out["graph_speedup"] >= 1.05, (
        f"graph submission speedup {out['graph_speedup']:.4f}x < 1.05x "
        f"vs wave-barriered bundles")
    return out


def graph_main(argv=None) -> int:
    """`python -m benchmarks.serving run_graph [--smoke]` — the CI
    graph-smoke entry point (gates are asserted inside `run_graph`)."""
    ap = argparse.ArgumentParser(prog="benchmarks.serving run_graph")
    ap.add_argument("--smoke", action="store_true",
                    help="short run for the tier-1 CI step")
    ap.add_argument("--steps", type=int, default=6)
    args = ap.parse_args(argv)
    rep = run_graph(GOLibrary(), steps=args.steps, smoke=args.smoke)
    print(f"# graph: {rep['graph_requests']} graphs "
          f"({rep['nodes_per_step']} nodes/step) over "
          f"{'+'.join(rep['tenants'])} | makespan "
          f"{rep['graph_makespan_s'] * 1e3:.3f}ms vs bundle "
          f"{rep['bundle_makespan_s'] * 1e3:.3f}ms = "
          f"{rep['graph_speedup']:.3f}x | {rep['cross_graph_groups']} "
          f"cross-graph groups, ready depth ≤{rep['max_ready_depth']}")
    return 0


# §17.4 adversarial shape: one tenant's monolithic prefill GEMM
# (~1.4 ms modeled, compute-bound — slicing it costs ~1% overhead)
# against many tenants' tiny decode GEMMs (~10 µs, memory-bound).
ABUSE_DESC = GemmDesc(16384, 8192, 1024)
LAT_DESCS = (GemmDesc(8, 4096, 1024), GemmDesc(8, 1024, 1024))


def run_adversarial(
    lib: GOLibrary,
    duration_s: float = 0.3,
    n_latency: int = 6,
    rate_hz: float = 200.0,
    abuse_rate_hz: float = 100.0,
    seed: int = 7,
) -> Dict[str, object]:
    """SLO stress test (DESIGN.md §17.4): replay the same adversarial
    trace — one abusive tenant submitting monolithic prefill GEMMs plus
    ``n_latency`` latency-sensitive tenants submitting small decode
    GEMMs — under the round-robin default and under slicing + EDF +
    budgeted flush, at equal offered load.  Virtual clock throughout, so
    the per-tenant p99s are deterministic.  The gated claim: the latency
    tenants' worst p99 improves ≥ 1.3x at equal total throughput."""
    trace = adversarial_trace(n_latency, rate_hz, duration_s,
                              abuse_rate_hz, seed=seed)
    window = 2e-4
    systems: Dict[str, Dict[str, object]] = {}
    for name in ("round-robin", "slo"):
        if name == "slo":
            cfg = RuntimeConfig(window_s=window, policy="edf", slicing=True,
                                flush_budget_s=2.5e-4)
        else:
            cfg = RuntimeConfig(window_s=window)
        rt = Runtime(ConcurrencyController(library=lib), cfg)
        for i in range(n_latency):
            rt.set_tenant_slo(f"lat{i}", TenantSLO(
                "latency", weight=4.0, p99_target_s=2e-3))
        rt.set_tenant_slo("abuse", TenantSLO(
            "batch", weight=1.0, p99_target_s=100e-3))
        rt.prewarm(list(LAT_DESCS) + [ABUSE_DESC])
        # Tune the piece class once so admission slicing never tunes live.
        rt.prewarm(list(slice_plan(ABUSE_DESC, 8).pieces))
        n_req = 0
        # Merge periodic flush ticks into the arrival stream: a live
        # dispatcher polls its queues; flushing only at arrivals would
        # make every arrival gap a service gap for BOTH systems and
        # drown the policy difference in replay artifacts.
        tick = window / 2
        horizon = trace[-1][0] + window
        ticks = [(i * tick, None) for i in range(1, int(horizon / tick) + 1)]
        for t, tenant in sorted(ticks + trace, key=lambda e: e[0]):
            rt.flush(now=t)
            if tenant is None:
                continue
            if tenant == "abuse":
                rt.submit(ABUSE_DESC, tenant=tenant, now=t)
                n_req += 1
            else:
                for d in LAT_DESCS:
                    rt.submit(d, tenant=tenant, now=t)
                    n_req += 1
        rt.drain(now=horizon)
        tele = rt.telemetry
        pct = tele.tenant_percentiles()
        systems[name] = {
            "requests": n_req,
            "tenants": pct,
            "latency_worst_p99_ms": max(
                v["p99_ms"] for k, v in pct.items() if k.startswith("lat")),
            "abuse_p99_ms": pct["abuse"]["p99_ms"],
            "throughput_req_per_s": n_req / max(rt.device_free_t, 1e-12),
            "sliced_ops": tele.sliced_ops,
            "slice_pieces": sum(tele.slice_counts.values()),
            "deferred_launches": tele.deferred_launches,
        }
    rr, slo = systems["round-robin"], systems["slo"]
    return {
        "trace": {"n_latency": n_latency, "rate_hz": rate_hz,
                  "duration_s": duration_s, "abuse_rate_hz": abuse_rate_hz,
                  "seed": seed, "arrivals": len(trace)},
        "systems": systems,
        "p99_gain": rr["latency_worst_p99_ms"]
        / max(slo["latency_worst_p99_ms"], 1e-9),
        "throughput_ratio": slo["throughput_req_per_s"]
        / max(rr["throughput_req_per_s"], 1e-12),
    }


def run_measured(cells: int = 3) -> Dict[str, object]:
    """Measured-vs-modeled columns (DESIGN.md §16): time the GO picks of
    a small decode GEMM grid through `core.measure` on the interpret
    backend, next to their modeled roofline times.  The microseconds are
    report-only (interpret-mode CPU calibrates candidate *ordering*, not
    absolute TPU latency — README "Measured vs modeled"); the trend gate
    consumes only the finite-cell count."""
    from repro.core.cost_model import group_time
    from repro.core.measure import Measurer, smoke_grid
    from repro.core.tuner import tune_gemm

    measurer = Measurer(warmup=1, repeats=3)
    grid: Dict[str, object] = {}
    finite = total = 0
    for d in smoke_grid(cells):
        e = tune_gemm(d)
        per = {}
        for cd in (1, 2):
            tile = e.tile_for_cd(cd)
            modeled = (isolated_time(d, tile) if cd == 1
                       else group_time([(d, tile)] * cd))
            m = measurer.measure_group(d, tile, cd)
            total += 1
            finite += int(m.finite)
            per[str(cd)] = {
                "modeled_us": round(modeled * 1e6, 3),
                "measured_us": round(m.time_s * 1e6, 1),
                "samples": m.n,
                "run_id": m.run_id,
            }
        grid[d.key()] = per
    return {"backend": measurer.backend, "measured_cells": total,
            "measured_finite_cells": finite, "grid": grid}


# §18 chaos benchmark: decode-ish GEMM pool with *integer-valued* f32
# operands, so every execution order, grouping, and kernel (pallas GO
# tile, isolated tile, XLA reference) produces bit-identical results —
# the property that lets the bitwise-correctness gate hold across
# fallback rungs (same trick as tests/test_kernel_stream_k.py).
CHAOS_DESCS = (GemmDesc(32, 128, 128, dtype="f32"),
               GemmDesc(64, 128, 128, dtype="f32"),
               GemmDesc(16, 256, 128, dtype="f32"))
CHAOS_RATES = (0.0, 0.01, 0.05)


def _chaos_operands(descs, seed: int = 0):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    ops = {}
    for j, d in enumerate(descs):
        ka = jax.random.fold_in(key, 2 * j)
        kb = jax.random.fold_in(key, 2 * j + 1)
        ops[d.key()] = (
            jax.random.randint(ka, (d.M, d.K), -4, 5).astype(jnp.float32),
            jax.random.randint(kb, (d.K, d.N), -4, 5).astype(jnp.float32))
    return ops


def run_chaos(
    rates=CHAOS_RATES,
    duration_s: float = 0.3,
    rate_hz: float = 400.0,
    seed: int = 13,
    smoke: bool = False,
) -> Dict[str, object]:
    """Chaos-hardened serving gate (DESIGN.md §18.5).

    Replays ONE Poisson decode-GEMM trace through the executing runtime
    at each injected per-launch fault rate (0% is the baseline), with a
    deterministic seed-keyed `FaultInjector` delivering a raise/NaN/stall
    mix.  Gated claims, asserted here and exported as trend metrics:

    - every submitted request completes at every fault rate (the
      fallback ladder never drops or crashes);
    - results are **bitwise-equal** to the fault-free run (integer-
      valued operands make all rungs exact);
    - the worst fault rate's p99 stays within 1.5x of fault-free on the
      modeled timeline (failed attempts charge real penalty time);
    - at the highest rate faults were actually delivered, and the
      telemetry fault counters reconcile exactly with the injector's
      audit log.
    """
    if smoke:
        # Short trace for the tier-1 CI step: too few launches for 1%/5%
        # to reliably deliver, so the smoke variant runs a hotter rate
        # set — the point is exercising every ladder rung, not the
        # canonical rates (those gate the full bench-trend run).
        duration_s, rate_hz = 0.06, 300.0
        rates = (0.0, 0.05, 0.25)
    descs = list(CHAOS_DESCS)
    operands = _chaos_operands(descs)
    arrivals = poisson_trace(rate_hz, duration_s, seed=seed)
    events = [(t, descs[i % len(descs)]) for i, t in enumerate(arrivals)]
    runs: Dict[str, Dict[str, object]] = {}
    baseline: List[np.ndarray] = []
    for rate in rates:
        inj = None
        if rate > 0:
            inj = FaultInjector(rules=[
                FaultRule("raise", rate * 0.4),
                FaultRule("nan", rate * 0.4),
                FaultRule("stall", rate * 0.2, stall_s=1e-3),
            ], seed=seed)
        rt = Runtime(
            ConcurrencyController(library=GOLibrary()),
            RuntimeConfig(window_s=1e-3, execute=True,
                          interpret=interpret_mode()),
            fault_injector=inj)
        rt.prewarm(descs)
        tickets = []
        for t, d in events:
            rt.flush(now=t)
            a, b = operands[d.key()]
            tickets.append(rt.submit(
                GemmRequest(desc=d, a=a, b=b), tenant="chaos", now=t))
        rt.drain(now=(events[-1][0] if events else 0.0) + 1e-3)
        # Half-open probes: release any quarantine after its cooldown so
        # the probe path is exercised whenever a quarantine happened.
        rt.process_retunes(
            now=rt.device_free_t + rt.config.quarantine_cooldown_s)
        tele = rt.telemetry
        results = [np.asarray(tk.result) for tk in tickets]
        if not baseline:
            baseline = results
        lat = np.asarray([tk.latency_s for tk in tickets], float)
        runs[f"{rate:g}"] = {
            "fault_rate": rate,
            "requests": len(tickets),
            "completed": tele.completed,
            "all_complete": (tele.completed == tele.submitted
                             and all(tk.done_t is not None
                                     and tk.result is not None
                                     for tk in tickets)),
            "bitwise_equal": bool(all(
                np.array_equal(r, b) for r, b in zip(results, baseline))),
            "injected": 0 if inj is None else len(inj.log),
            "faults": dict(tele.faults),
            "fallbacks": dict(tele.fallbacks),
            "quarantines": tele.quarantines,
            "plan_evictions": tele.quarantine_evictions,
            "probes": tele.probes,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
        }
    base_p99 = runs[f"{rates[0]:g}"]["p99_ms"]
    for r in runs.values():
        r["p99_ratio"] = round(r["p99_ms"] / max(base_p99, 1e-12), 4)
    worst = runs[f"{max(rates):g}"]
    out = {
        "rates": list(rates),
        "events": len(events),
        "smoke": smoke,
        "runs": runs,
        "completed_total": sum(r["completed"] for r in runs.values()),
        "fallbacks_total": sum(
            sum(r["fallbacks"].values()) for r in runs.values()),
        "worst_p99_ratio": max(r["p99_ratio"] for r in runs.values()),
    }
    # ------------------------------------------------------------- gates
    for tag, r in runs.items():
        assert r["all_complete"], f"chaos rate {tag}: dropped requests"
        assert r["bitwise_equal"], (
            f"chaos rate {tag}: results diverge from fault-free run")
        # Reconcile telemetry against the injector's audit log: every
        # delivered fault produced exactly one recorded failed attempt,
        # and nothing failed that was not injected.
        assert sum(r["faults"].values()) == r["injected"], (
            f"chaos rate {tag}: {sum(r['faults'].values())} faults "
            f"recorded vs {r['injected']} injected")
        assert r["faults"].get("error", 0) == 0, (
            f"chaos rate {tag}: genuine (non-injected) launch errors")
    assert worst["injected"] > 0, (
        "highest chaos rate delivered zero faults — trace too short for "
        "the gate to mean anything")
    assert out["worst_p99_ratio"] <= 1.5, (
        f"chaos p99 degradation {out['worst_p99_ratio']:.3f}x > 1.5x")
    return out


def chaos_main(argv=None) -> int:
    """`python -m benchmarks.serving run_chaos [--smoke]` — the CI
    chaos-smoke entry point (gates are asserted inside `run_chaos`)."""
    ap = argparse.ArgumentParser(prog="benchmarks.serving run_chaos")
    ap.add_argument("--smoke", action="store_true",
                    help="short trace for the tier-1 CI step")
    args = ap.parse_args(argv)
    rep = run_chaos(smoke=args.smoke)
    for tag, r in rep["runs"].items():
        print(f"# chaos rate={tag}: {r['completed']}/{r['requests']} "
              f"complete, {r['injected']} injected, "
              f"fallbacks={r['fallbacks']}, quarantines={r['quarantines']}, "
              f"probes={r['probes']}, p99x={r['p99_ratio']}")
    print(f"# chaos OK: bitwise-equal at all rates, worst p99 "
          f"{rep['worst_p99_ratio']}x")
    return 0


def verify_execute() -> None:
    """End-to-end kernel check: one reduced-config decode flush through the
    real pallas kernels (interpret mode off-TPU) vs the XLA reference."""
    import jax
    import jax.numpy as jnp

    cfg = get_arch("stablelm-3b").reduced()
    lib = GOLibrary()
    ctrl = ConcurrencyController(library=lib)
    rt = Runtime(ctrl, RuntimeConfig(window_s=0.0, execute=True,
                                     interpret=interpret_mode()))
    key = jax.random.PRNGKey(0)
    tickets = []
    # Three concurrent decode streams so the planner emits grouped launches.
    step = decode_step_requests(ctrl, cfg, batch=4, dtype="f32")
    for stream in range(3):
        for i, req in enumerate(step):
            d = req.desc
            a = jax.random.normal(jax.random.fold_in(key, 1000 * stream + 2 * i),
                                  (d.M, d.K), jnp.float32)
            b = jax.random.normal(jax.random.fold_in(key, 1000 * stream + 2 * i + 1),
                                  (d.K, d.N), jnp.float32)
            tickets.append(rt.submit(
                GemmRequest(desc=d, a=a, b=b, tag=req.tag),
                tenant=f"stream{stream}", now=0.0))
    rt.drain(now=1.0)
    for tk in tickets:
        ref = tk.request.a @ tk.request.b
        np.testing.assert_allclose(tk.result, ref, rtol=3e-4, atol=3e-4)
    modes = rt.telemetry.mode_counts()
    how = "interpret" if interpret_mode() else "compiled"
    print(f"# verify: {len(tickets)} GEMMs executed through pallas "
          f"({how}) and matched reference; modes={modes}")


def main(argv=None) -> Dict[str, Dict[str, Dict[str, float]]]:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv[:1] == ["run_chaos"]:
        sys.exit(chaos_main(argv[1:]))
    if argv[:1] == ["run_graph"]:
        sys.exit(graph_main(argv[1:]))
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=0.5,
                    help="trace duration in virtual seconds")
    ap.add_argument("--rate", type=float, default=300.0,
                    help="decode steps/s per tenant")
    ap.add_argument("--trace", choices=("poisson", "bursty", "both"),
                    default="both")
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--mixed-ops", action="store_true",
                    help="also replay heterogeneous decode bundles spanning "
                         "all four kernel families (DESIGN.md §14)")
    args = ap.parse_args(argv)

    RESULTS.mkdir(exist_ok=True)
    lib = GOLibrary(RESULTS / "serving_golib.json")

    kinds = ("poisson", "bursty") if args.trace == "both" else (args.trace,)
    lines = ["trace,system,requests,p50_ms,p95_ms,p99_ms,throughput_steps_s,"
             "speedup_vs_seq,plan_cache_hit_rate,mean_cd"]
    print(lines[0])
    results: Dict[str, Dict[str, Dict[str, float]]] = {}
    for kind in kinds:
        res = run_trace(lib, kind, args.rate, args.duration)
        results[kind] = res
        for system, r in res.items():
            line = (f"{kind},{system},{r['requests']},{r['p50_ms']:.3f},"
                    f"{r['p95_ms']:.3f},{r['p99_ms']:.3f},"
                    f"{r['throughput_steps_per_s']:.0f},"
                    f"{r['speedup_vs_seq']:.3f},{r['hit_rate']:.3f},"
                    f"{r['mean_cd']:.2f}")
            print(line, flush=True)
            lines.append(line)
    (RESULTS / "serving.csv").write_text("\n".join(lines) + "\n")

    flags = {"duration": args.duration, "rate": args.rate,
             "trace": args.trace, "mixed_ops": bool(args.mixed_ops)}

    mixed = None
    if args.mixed_ops:
        mixed = run_mixed_ops(lib)
        print(f"# mixed-ops: {mixed['bundle_ops_per_step']} ops/step over "
              f"{'+'.join(mixed['tenants'])} spanning "
              f"{len(mixed['families'])} families | mean CD "
              f"{mixed['mean_cd']} | modeled speedup vs sequential "
              f"{mixed['speedup_vs_sequential']:.2f}x | steady hit rate "
              f"{mixed['hit_rate_steady']:.3f}")
        assert mixed["speedup_vs_sequential"] > 1.05, (
            f"mixed-family co-scheduling speedup "
            f"{mixed['speedup_vs_sequential']:.3f} <= 1.05x")
        assert mixed["hit_rate_steady"] > 0.9

    adversarial = run_adversarial(lib)
    rr = adversarial["systems"]["round-robin"]
    slo = adversarial["systems"]["slo"]
    print(f"# adversarial: latency worst-p99 "
          f"{rr['latency_worst_p99_ms']:.3f}ms (round-robin) -> "
          f"{slo['latency_worst_p99_ms']:.3f}ms (slicing+EDF) = "
          f"{adversarial['p99_gain']:.2f}x gain | "
          f"{slo['sliced_ops']} ops sliced into {slo['slice_pieces']} "
          f"pieces, {slo['deferred_launches']} launches deferred | "
          f"throughput ratio {adversarial['throughput_ratio']:.3f}")
    assert adversarial["p99_gain"] >= 1.3, (
        f"adversarial p99 gain {adversarial['p99_gain']:.3f} < 1.3x")
    assert abs(1.0 - adversarial["throughput_ratio"]) <= 0.05, (
        f"slicing+EDF throughput deviates >5%: "
        f"ratio {adversarial['throughput_ratio']:.4f}")

    measured = run_measured()
    print(f"# measured: {measured['measured_finite_cells']}/"
          f"{measured['measured_cells']} cells finite on "
          f"{measured['backend']}")
    assert measured["measured_finite_cells"] == measured["measured_cells"], \
        "measurement harness produced non-finite/zero timings"

    chaos = run_chaos()
    worst = chaos["runs"][f"{max(chaos['rates']):g}"]
    print(f"# chaos: {chaos['completed_total']} requests over rates "
          f"{chaos['rates']} all complete + bitwise-equal | "
          f"{worst['injected']} faults at {max(chaos['rates']):.0%} -> "
          f"{chaos['fallbacks_total']} fallbacks, "
          f"{worst['quarantines']} quarantines | worst p99 "
          f"{chaos['worst_p99_ratio']}x")

    graph = run_graph(lib)
    print(f"# graph: {graph['graph_requests']} graphs "
          f"({graph['nodes_per_step']} nodes/step) over "
          f"{'+'.join(graph['tenants'])} | dataflow vs wave-barriered "
          f"bundles {graph['graph_speedup']:.3f}x | "
          f"{graph['cross_graph_groups']} cross-graph groups, "
          f"ready depth ≤{graph['max_ready_depth']}")

    _write_bench_json(results, mixed, measured, adversarial, chaos, graph,
                      flags)
    lib.save()

    if not args.no_verify:
        verify_execute()

    if "poisson" in results and args.duration >= 0.1:
        gold = results["poisson"]["goldyloc"]
        assert gold["hit_rate_steady"] > 0.9, (
            f"steady-state plan-cache hit rate "
            f"{gold['hit_rate_steady']:.3f} <= 0.9")
        assert gold["speedup_vs_seq"] >= 1.2, (
            f"modeled speedup {gold['speedup_vs_seq']:.3f} < 1.2x")
        print(f"# acceptance: steady-state hit_rate="
              f"{gold['hit_rate_steady']:.3f} (overall "
              f"{gold['hit_rate']:.3f}) speedup="
              f"{gold['speedup_vs_seq']:.2f}x ✓")
    return results


def _write_bench_json(results, mixed, measured, adversarial, chaos,
                      graph, flags) -> None:
    """`results/BENCH_serving.json`: the serving benchmark's count-based
    metric record.  ``trend_metrics`` is the generic contract consumed by
    `benchmarks/trend.py` (the CI bench-trend gate): each entry declares
    its value and which direction is better, so the checker needs no
    per-benchmark knowledge.  Everything here is derived from the modeled
    virtual-clock replay — deterministic, flake-free on shared runners.

    ``flags`` (the arguments that shaped the run) are recorded in the
    blob: several metrics are raw counts that scale with duration/trace
    selection, so `trend.py` refuses to compare reports produced under
    different flags.  Regenerate the committed baseline ONLY with the
    canonical CI command:

        PYTHONPATH=src python -m benchmarks.serving --duration 0.1 \\
            --trace poisson --mixed-ops
    """
    trend: Dict[str, Dict[str, object]] = {}
    for kind, res in results.items():
        gold = res.get("goldyloc")
        if not gold:
            continue
        trend[f"{kind}_requests"] = {
            "value": gold["requests"], "better": "higher"}
        trend[f"{kind}_speedup_vs_seq"] = {
            "value": round(gold["speedup_vs_seq"], 4), "better": "higher"}
        trend[f"{kind}_hit_rate_steady"] = {
            "value": round(gold["hit_rate_steady"], 4), "better": "higher"}
        trend[f"{kind}_mean_cd"] = {
            "value": round(gold["mean_cd"], 4), "better": "higher"}
    if mixed is not None:
        trend["mixed_families"] = {
            "value": len(mixed["families"]), "better": "higher"}
        trend["mixed_bundle_ops_per_step"] = {
            "value": mixed["bundle_ops_per_step"], "better": "higher"}
        trend["mixed_speedup_vs_sequential"] = {
            "value": round(mixed["speedup_vs_sequential"], 4),
            "better": "higher"}
        trend["mixed_hit_rate_steady"] = {
            "value": mixed["hit_rate_steady"], "better": "higher"}
        trend["mixed_mean_cd"] = {
            "value": mixed["mean_cd"], "better": "higher"}
    # Measured-harness coverage (§16): count-based only — the measured
    # microseconds live in the report but are never trend-gated.
    trend["measured_cells"] = {
        "value": measured["measured_finite_cells"], "better": "higher"}
    # §17.4 SLO gate: deterministic virtual-clock ratios and counts.
    slo = adversarial["systems"]["slo"]
    trend["adversarial_p99_gain"] = {
        "value": round(adversarial["p99_gain"], 4), "better": "higher"}
    trend["adversarial_throughput_ratio"] = {
        "value": round(adversarial["throughput_ratio"], 4),
        "better": "higher"}
    trend["adversarial_requests"] = {
        "value": slo["requests"], "better": "higher"}
    trend["adversarial_slice_pieces"] = {
        "value": slo["slice_pieces"], "better": "higher"}
    # §18.5 chaos gate: completions must never regress (the ladder keeps
    # every request alive), fallbacks must not silently vanish (that
    # would mean injection stopped exercising the ladder), and p99
    # degradation under the worst fault rate is bounded.
    trend["chaos_completed"] = {
        "value": chaos["completed_total"], "better": "higher"}
    trend["chaos_fallbacks"] = {
        "value": chaos["fallbacks_total"], "better": "higher"}
    trend["chaos_worst_p99_ratio"] = {
        "value": chaos["worst_p99_ratio"], "better": "lower"}
    # §19.4 dataflow gate: graph submission must keep beating the
    # wave-barriered bundle ceiling, the readiness tracker must keep
    # exposing multi-node windows, and every submitted graph counts.
    trend["graph_speedup"] = {
        "value": round(graph["graph_speedup"], 4), "better": "higher"}
    trend["ready_set_depth"] = {
        "value": graph["max_ready_depth"], "better": "higher"}
    trend["graph_requests"] = {
        "value": graph["graph_requests"], "better": "higher"}
    blob = {
        "flags": flags,
        "traces": results,
        "mixed_ops": mixed,
        "measured": measured,
        "adversarial": adversarial,
        "chaos": chaos,
        "graph": graph,
        "trend_metrics": trend,
    }
    out = RESULTS / "BENCH_serving.json"
    out.write_text(json.dumps(blob, indent=1))
    print(f"# wrote {out}")


if __name__ == "__main__":
    main()
