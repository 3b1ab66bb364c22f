"""Serving-runtime telemetry — DESIGN.md §10.3.

Records what the dynamic logic actually did under live load, the data the
paper reads off its CP counters: per-group concurrency degree and mode,
modeled vs achieved latency, plan-cache effectiveness (how much of
``CP_OVERHEAD_S`` steady-state traffic amortizes away), and queue-depth
histograms per compatibility class.

Everything is plain Python so the telemetry can run inside the dispatch
path without touching the device.

Two clocks meet here.  Latencies (`tenant_lat`, `GroupRecord` times) run
on the runtime's own clock, which replay drives virtually, so they are
modeled.  The host cost of the dispatch path (`host_s`, `host_calls`)
and the queue wait (`queue_wait_s`) always run on `time.perf_counter`:
they are what a decode step really pays on the host.
"""
from __future__ import annotations

import math
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Optional

PHASES = ("submit", "plan", "launch", "record")   # of the dispatch path, in order


@dataclass
class GroupRecord:
    """One launched group (one `GroupPlan` bound to live requests)."""

    flush_id: int
    class_key: str
    tenants: List[str]
    cd: int
    mode: str                       # "grouped" | "ragged" | "single" | "fused"
    modeled_time_s: float
    achieved_time_s: Optional[float] = None   # wall clock when executed
    cache_hit: bool = False
    # Which fallback rung completed the launch (§18.2): None for the
    # planned schedule, else "retry" | "legacy" | "reference".
    fallback: Optional[str] = None
    # Distinct graph handles with a node in this launch (§19.3); ≥2
    # entries is the cross-request overlap the dataflow executor exists
    # to create.
    graph_ids: tuple = ()
    # Keys of the GO tiles the planned launch runs (one per member of a
    # mixed group, else one).
    tiles: tuple = ()

    @property
    def model_error(self) -> Optional[float]:
        """achieved / modeled — >1 means the model was optimistic."""
        if self.achieved_time_s is None or self.modeled_time_s <= 0:
            return None
        return self.achieved_time_s / self.modeled_time_s


@dataclass
class Telemetry:
    groups: List[GroupRecord] = field(default_factory=list)
    cache_hits: int = 0
    cache_misses: int = 0
    prewarmed_plans: int = 0
    flushes: int = 0
    submitted: int = 0
    completed: int = 0
    # depth observed per compatibility class at each flush
    depth_hist: Counter = field(default_factory=Counter)
    cp_overhead_paid_s: float = 0.0
    cp_overhead_saved_s: float = 0.0
    # Dispatch fast-path counters (DESIGN.md §10/§13).  `sig_resorts`
    # counts every full canonical-signature sort (the runtime's
    # `_canonical_sort` — today only offline prewarm planning pays one);
    # `flush_sig_resorts` / `flush_evals` are the portions attributable
    # to flush() itself, which must both stay ZERO on the fast path —
    # the admission-sorted queues make a flush-path sort structurally
    # unnecessary, and these deltas catch any regression that
    # reintroduces one.
    flush_evals: int = 0
    last_flush_evals: int = 0
    sig_resorts: int = 0
    flush_sig_resorts: int = 0
    # Multi-tenant SLO accounting (DESIGN.md §17): completed-request
    # latencies per tenant (parents count once, not per slice) and how
    # many pieces admission slicing produced per tenant.
    tenant_lat: Dict[str, List[float]] = field(default_factory=dict)
    slice_counts: Counter = field(default_factory=Counter)
    sliced_ops: int = 0
    deferred_launches: int = 0
    # Fault-tolerance accounting (DESIGN.md §18): failed launch attempts
    # by kind ("raise" | "nan" | "stall" | "error"), successful fallback
    # completions by rung, quarantine/probe events, and cached plans
    # evicted by quarantines.  These reconcile with the FaultInjector's
    # audit log (property-tested in tests/test_chaos.py).
    faults: Counter = field(default_factory=Counter)
    fallbacks: Counter = field(default_factory=Counter)
    quarantines: int = 0
    quarantine_evictions: int = 0
    probes: int = 0
    # Dataflow-graph accounting (DESIGN.md §19.3).  A graph is ONE
    # logical request — `submitted`/`completed`/`tenant_lat` count it
    # once, at sink-node completion — and these track the graph-specific
    # dimensions: how many graphs/nodes were admitted, and the ready-set
    # depth each mixed concurrency window drew from.
    graphs_submitted: int = 0
    graphs_completed: int = 0
    graph_nodes: int = 0
    ready_depth_hist: Counter = field(default_factory=Counter)
    max_ready_depth: int = 0
    # Host clock (`time.perf_counter`), also under a virtual runtime
    # clock: seconds and calls per phase of the dispatch path ("submit",
    # "plan", "launch", "record"), and the wait from a ticket's submit
    # to the flush that binds it.  Sums, so nothing grows per launch.
    host_s: Dict[str, float] = field(default_factory=lambda: dict.fromkeys(PHASES, 0.0))
    host_calls: Dict[str, int] = field(default_factory=lambda: dict.fromkeys(PHASES, 0))
    queue_wait_s: float = 0.0
    queue_waits: int = 0
    queue_wait_max_s: float = 0.0

    # ------------------------------------------------------------- record
    def record_submit(self, n: int = 1) -> None:
        self.submitted += n

    def record_flush(self, queue_depths: Dict[str, int]) -> None:
        self.flushes += 1
        for depth in queue_depths.values():
            self.depth_hist[_bucket(depth)] += 1

    def record_plan(self, hit: bool, overhead_s: float) -> None:
        if hit:
            self.cache_hits += 1
            self.cp_overhead_saved_s += overhead_s
        else:
            self.cache_misses += 1
            self.cp_overhead_paid_s += overhead_s

    def record_sig_resort(self, n: int = 1) -> None:
        """A full canonical-signature sort was performed (offline prewarm
        planning today; anything on the flush path is a regression)."""
        self.sig_resorts += n

    def record_flush_fastpath(self, evals: int, resorts: int) -> None:
        """Cost-model evaluations / signature re-sorts attributable to
        one flush()."""
        self.last_flush_evals = evals
        self.flush_evals += evals
        self.flush_sig_resorts += resorts

    def record_prewarm_plan(self, overhead_s: float) -> None:
        """Offline (pre-traffic) plan derivation: paid, but not an online
        cache miss — keeps the live hit rate meaningful under prewarm."""
        self.prewarmed_plans += 1
        self.cp_overhead_paid_s += overhead_s

    def record_group(self, rec: GroupRecord) -> None:
        self.groups.append(rec)

    def record_latency(self, tenant: str, latency_s: float) -> None:
        """One *logical* request completed (a sliced op records once, at
        parent completion — per-piece latencies are an implementation
        detail the tenant never observes).  ``completed`` therefore
        matches ``submitted`` in steady state even under slicing."""
        self.completed += 1
        self.tenant_lat.setdefault(tenant, []).append(latency_s)

    def record_slices(self, tenant: str, parts: int) -> None:
        """Admission sliced one op into ``parts`` pieces (§17.2)."""
        self.sliced_ops += 1
        self.slice_counts[tenant] += parts

    def record_deferred(self, n: int = 1) -> None:
        """Launches pushed past a flush budget to the next flush (§17.3)."""
        self.deferred_launches += n

    def record_fault(self, kind: str) -> None:
        """One failed launch attempt (§18.2) — before any fallback."""
        self.faults[kind] += 1

    def record_fallback(self, rung: str) -> None:
        """One launch completed by the given fallback rung (§18.2)."""
        self.fallbacks[rung] += 1

    def record_quarantine(self, evicted_plans: int = 0) -> None:
        """The circuit breaker quarantined one (family, class, tile)
        (§18.3), evicting ``evicted_plans`` cached plans."""
        self.quarantines += 1
        self.quarantine_evictions += evicted_plans

    def record_probe(self, n: int = 1) -> None:
        """Half-open probes: quarantines released after cooldown (§18.3)."""
        self.probes += n

    def record_graph_submit(self, nodes: int) -> None:
        """One `OpGraph` admitted with ``nodes`` nodes (§19.3).  The
        caller records the single logical submit separately."""
        self.graphs_submitted += 1
        self.graph_nodes += nodes

    def record_graph_complete(self) -> None:
        """One graph's sink completed — its latency was just recorded as
        the graph's single logical completion (§19.3)."""
        self.graphs_completed += 1

    def record_ready_depth(self, depth: int) -> None:
        """Graph nodes available to one mixed concurrency window — the
        dataflow ready-set depth (§19.3)."""
        self.ready_depth_hist[_bucket(depth)] += 1
        if depth > self.max_ready_depth:
            self.max_ready_depth = depth

    def record_host(self, phase: str, seconds: float) -> None:
        """One call of a dispatch-path phase took ``seconds`` of host time."""
        self.host_s[phase] += seconds
        self.host_calls[phase] += 1

    def record_flush_host(self, plan_s: float, launch_s: float, record_s: float,
                          waits: int, wait_s: float, wait_max_s: float) -> None:
        """One flush that bound launches, on the host clock: its seconds
        by phase, and the ``waits`` tickets it bound, which waited
        ``wait_s`` seconds in all since their submit, the longest
        ``wait_max_s``."""
        host, calls = self.host_s, self.host_calls
        host["plan"] += plan_s
        host["launch"] += launch_s
        host["record"] += record_s
        calls["plan"] += 1
        calls["launch"] += 1
        calls["record"] += 1
        if waits:
            self.queue_wait_s += wait_s
            self.queue_waits += waits
            if wait_max_s > self.queue_wait_max_s:
                self.queue_wait_max_s = wait_max_s

    @property
    def fault_events(self) -> int:
        return sum(self.faults.values())

    @property
    def fallback_events(self) -> int:
        return sum(self.fallbacks.values())

    # ------------------------------------------------------------ derive
    def cache_hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    def steady_state_hit_rate(self, skip_frac: float = 0.5) -> float:
        """Plan-cache hit rate excluding the warm-up: only groups from the
        last ``1 - skip_frac`` of flushes count.  This is the number the
        paper's steady-state claim is about — cold-start misses are a
        one-time cost already reported via `cp_overhead_paid_s`."""
        if not self.groups:
            return 0.0
        cutoff = self.groups[-1].flush_id * skip_frac
        tail = [g for g in self.groups if g.flush_id > cutoff]
        return sum(g.cache_hit for g in tail) / max(len(tail), 1)

    def queue_depth_histogram(self) -> Dict[str, int]:
        """Power-of-two depth buckets, e.g. {"1": 12, "2-3": 40, "4-7": 9}."""
        return {k: self.depth_hist[k] for k in sorted(self.depth_hist, key=_bucket_lo)}

    def mode_counts(self) -> Dict[str, int]:
        return dict(Counter(g.mode for g in self.groups))

    def mean_cd(self) -> float:
        return (
            sum(g.cd for g in self.groups) / len(self.groups)
            if self.groups else 0.0
        )

    def max_cd(self) -> int:
        """Highest CD_exec launched — under a sharded mesh this must stay
        ≤ the derated per-shard slot budget (DESIGN.md §12.5)."""
        return max((g.cd for g in self.groups), default=0)

    def modeled_busy_time_s(self) -> float:
        return sum(g.modeled_time_s for g in self.groups)

    def class_ratios(self) -> Dict[str, Dict[str, float]]:
        """Per-class modeled-vs-achieved aggregates — the calibration
        input (DESIGN.md §16).  `GroupRecord.model_error` used to be
        computed and dropped; here every executed group's ratio is
        folded into its compatibility class:

        - ``n``: executed groups with a usable ratio;
        - ``geomean_ratio``: exp(mean log ratio) — >1 ⇒ the model is
          optimistic for this class (the multiplicative bias a
          `CostCalibrator` fits);
        - ``mean_abs_log``: mean |log ratio| — the drift statistic.
        """
        acc: Dict[str, List[float]] = {}
        for g in self.groups:
            r = g.model_error
            # Non-finite ratios (hung/faulted launches, §18) carry no
            # calibration signal and would poison every aggregate.
            if r is not None and r > 0 and math.isfinite(r):
                acc.setdefault(g.class_key, []).append(math.log(r))
        return {
            k: {
                "n": len(logs),
                "geomean_ratio": round(math.exp(sum(logs) / len(logs)), 4),
                "mean_abs_log": round(sum(abs(x) for x in logs) / len(logs), 4),
            }
            for k, logs in sorted(acc.items())
        }

    def cross_graph_groups(self) -> int:
        """Launched groups whose members came from ≥2 distinct graphs —
        the §19 acceptance signal: one request's nodes sharing a
        concurrency window with another's."""
        return sum(1 for g in self.groups if len(g.graph_ids) >= 2)

    def ready_depth_histogram(self) -> Dict[str, int]:
        """Power-of-two buckets of per-window graph ready-set depth."""
        return {k: self.ready_depth_hist[k]
                for k in sorted(self.ready_depth_hist, key=_bucket_lo)}

    def host_us(self) -> Dict[str, float]:
        """Host microseconds per call of each dispatch-path phase called."""
        return {k: round(1e6 * self.host_s[k] / n, 3)
                for k, n in self.host_calls.items() if n}

    def queue_wait_us(self) -> Dict[str, float]:
        """Mean and longest host wait from submit to binding, in µs."""
        mean = self.queue_wait_s / self.queue_waits if self.queue_waits else 0.0
        return {"mean": round(1e6 * mean, 3),
                "max": round(1e6 * self.queue_wait_max_s, 3)}

    def tenant_percentiles(self) -> Dict[str, Dict[str, float]]:
        """Per-tenant p50/p95/p99 latency (ms, nearest-rank on the sorted
        sample) plus count — the §17 metric that matters at many users.
        Plain Python, deterministic, safe inside the dispatch path."""
        out: Dict[str, Dict[str, float]] = {}
        for tenant in sorted(self.tenant_lat):
            lat = sorted(self.tenant_lat[tenant])
            if not lat:
                continue
            out[tenant] = {
                "n": len(lat),
                "p50_ms": round(_nearest_rank(lat, 0.50) * 1e3, 4),
                "p95_ms": round(_nearest_rank(lat, 0.95) * 1e3, 4),
                "p99_ms": round(_nearest_rank(lat, 0.99) * 1e3, 4),
            }
        return out

    def snapshot(self) -> Dict[str, object]:
        """Alias of `summary()`."""
        return self.summary()

    def summary(self) -> Dict[str, object]:
        return {
            "submitted": self.submitted,
            "completed": self.completed,
            "flushes": self.flushes,
            "groups": len(self.groups),
            "mean_cd": round(self.mean_cd(), 3),
            "max_cd": self.max_cd(),
            "modes": self.mode_counts(),
            "plan_cache_hit_rate": round(self.cache_hit_rate(), 4),
            "flush_evals": self.flush_evals,
            "sig_resorts": self.sig_resorts,
            "flush_sig_resorts": self.flush_sig_resorts,
            "prewarmed_plans": self.prewarmed_plans,
            "cp_overhead_paid_us": round(self.cp_overhead_paid_s * 1e6, 2),
            "cp_overhead_saved_us": round(self.cp_overhead_saved_s * 1e6, 2),
            "modeled_busy_time_us": round(self.modeled_busy_time_s() * 1e6, 2),
            "queue_depths": self.queue_depth_histogram(),
            "class_ratios": self.class_ratios(),
            "tenants": self.tenant_percentiles(),
            "slice_counts": dict(self.slice_counts),
            "sliced_ops": self.sliced_ops,
            "deferred_launches": self.deferred_launches,
            "faults": dict(self.faults),
            "fallbacks": dict(self.fallbacks),
            "quarantines": self.quarantines,
            "quarantine_evictions": self.quarantine_evictions,
            "probes": self.probes,
            "graphs_submitted": self.graphs_submitted,
            "graphs_completed": self.graphs_completed,
            "graph_nodes": self.graph_nodes,
            "cross_graph_groups": self.cross_graph_groups(),
            "ready_depths": self.ready_depth_histogram(),
            "max_ready_depth": self.max_ready_depth,
            "host_us": self.host_us(),
            "host_calls": {k: n for k, n in self.host_calls.items() if n},
            "queue_wait_us": self.queue_wait_us(),
        }


def _nearest_rank(sorted_lat: List[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted sample."""
    i = max(0, math.ceil(q * len(sorted_lat)) - 1)
    return sorted_lat[i]


def _bucket(depth: int) -> str:
    if depth <= 0:
        return "0"
    lo = 1
    while lo * 2 <= depth:
        lo *= 2
    return str(lo) if lo == 1 else f"{lo}-{2 * lo - 1}"


def _bucket_lo(name: str) -> int:
    return int(name.split("-")[0])
