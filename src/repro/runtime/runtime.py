"""Online concurrent-GEMM serving runtime — DESIGN.md §10.

The seed's `ConcurrencyController` is one-shot: every `plan()` call
re-derives the schedule from scratch, so nothing exercised the paper's
actual scenario — *varying available parallelism under live load* (§4.4).
This module is the missing online layer:

- `submit()` admits `GemmRequest`s (tagged with a tenant/stream id) into
  **per-compatibility-class queues** (`core.scheduler.compat_key`, §6.7).
  Admission does the per-ticket work ONCE: the class key is a memoized
  lookup and the ticket is bisect-inserted at its canonical position, so
  each class queue maintains its plan-cache signature incrementally.
- `flush()` runs the lightweight dynamic logic on the queue heads exactly
  as the paper's CP does — ``CD_exec = min(CD_predicted, available)`` —
  but through a **plan cache** keyed by the queue signature (canonically
  sorted desc keys + available slots), so steady-state traffic skips
  re-planning and re-tuning entirely and `CP_OVERHEAD_S` is amortized.
  A cache-hit flush performs **zero cost-model evaluations and zero
  signature re-sorts** (asserted by telemetry counters and
  `benchmarks/tuning.py`) — this is what makes the dynamic logic
  "lightweight" in the paper's CP-resident sense (DESIGN.md §13).
- launches are interleaved **round-robin across compatibility classes**,
  so one tenant's large GEMMs cannot starve another tenant's small ones.
- `drain()` force-flushes until the queues are empty.
- `submit()` is polymorphic (§19): a single op, a §14 bundle, or an
  `runtime.graph.OpGraph` — the dataflow path, where a readiness tracker
  releases nodes into the mixed-op pool as predecessors complete, so one
  request's attention can share a concurrency window with another
  request's experts.  Every kind returns one `Ticket`.

The runtime keeps a modeled device timeline (`device_free_t`) so latency
accounting works identically in closed-loop replay (virtual clock, the
serving benchmark) and live shadow dispatch (wall clock, the serve loop).
Set ``RuntimeConfig.execute=True`` to also run every launch through the
real pallas kernels (`ConcurrencyController.execute_plan`).

Each phase of the dispatch path is a `jax.profiler` span
(``runtime.submit``, ``runtime.plan``, ``runtime.launch``,
``runtime.record``), so a profiler trace places it on the device's
clock, and its host-clock time is summed in `Telemetry.host_s`.
"""
from __future__ import annotations

import bisect
import math
import time
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.cost_model import (
    EVAL_COUNTER,
    SLICE_OVERHEAD_S,
    isolated_time,
)
from repro.core.gemm_desc import GemmDesc
from repro.core.op_desc import SlicePlan, family_of, slice_plan
from repro.core.scheduler import (
    CP_OVERHEAD_S,
    ConcurrencyController,
    GemmRequest,
    GroupPlan,
    Schedule,
    bind_operands,
    compat_key,
    execute_schedule,
)
from repro.runtime.faults import (
    CircuitBreaker,
    FaultInjector,
    NonFiniteOutput,
    fault_kind,
)
from repro.runtime.graph import GraphState, OpGraph
from repro.runtime.telemetry import GroupRecord, Telemetry

Signature = Tuple[Tuple[str, ...], int]

# Class key of the heterogeneous-bundle queue (§14).  The "!" cannot
# occur in a `compat_key`, so bundle tickets never collide with a
# per-class GEMM queue; its plan-cache signatures are prefixed with the
# same marker so a bundle of (say) only GEMMs cannot alias a class
# queue's cached per-class plan.
MIXED_CLASS = "mixed!"


@dataclass
class RuntimeConfig:
    window_s: float = 2e-3          # batching window before a class is ripe
    plan_cache_capacity: int = 512  # LRU entries (queue signatures)
    execute: bool = False           # run launches through the real kernels
    interpret: bool | None = None   # forwarded to pallas when executing
    # SLO policy (DESIGN.md §17).  The defaults reproduce the pre-SLO
    # runtime bit-for-bit: round-robin class service, no admission
    # slicing, unbounded flushes.
    policy: str = "round-robin"     # "round-robin" | "edf"
    slicing: bool = False           # slice oversized ops at admission
    flush_budget_s: float | None = None  # bind ≤ this much modeled work/flush
    slice_budget_frac: float = 0.5  # slice when iso time > budget * frac
    max_slices: int = 8             # admission never slices finer than this
    # Fault tolerance (DESIGN.md §18).  The defaults change nothing on
    # the healthy path: the ladder only engages when an attempt fails.
    max_retries: int = 1            # same-plan retries before re-planning
    quarantine_strikes: int = 3     # consecutive failures → quarantine
    quarantine_cooldown_s: float = 0.5   # then half-open probe (§18.3)


@dataclass(frozen=True)
class TenantSLO:
    """A tenant's service objective (DESIGN.md §17.2).

    ``latency_class`` is "latency" (decode-style, deadline-driven) or
    "batch" (throughput-driven, deadline = p99 target but outranked);
    ``weight`` breaks deadline ties — heavier tenants bind first;
    ``p99_target_s`` turns each submit into an absolute EDF deadline
    (``submit_t + p99_target_s``), which is what makes the ordering
    starvation-free: a waiting ticket's deadline only gets *earlier*
    relative to fresh arrivals."""

    latency_class: str = "batch"
    weight: float = 1.0
    p99_target_s: float = 50e-3

    @property
    def rank(self) -> int:
        return 0 if self.latency_class == "latency" else 1


DEFAULT_SLO = TenantSLO()


@dataclass
class Ticket:
    """The ONE handle type every submission kind returns (§19.2).

    ``kind`` says what the handle stands for — callers never branch on
    it, but the runtime's completion plumbing does:

    - ``"op"``: a single op (the classic ticket; ``request`` set).
    - ``"node"``: one graph node.  ``node``/``graph`` link it to its
      name and its graph handle; ``logical=False`` (the *graph* is the
      logical request, §19.3) and ``request`` is bound at release time,
      once the predecessors' outputs are wired in.
    - ``"bundle"``: aggregate over ``members`` (each an ordinary logical
      "op" ticket, preserving §14/§17 per-member accounting);
      ``request`` is None.
    - ``"graph"``: aggregate over ``nodes`` (name → node ticket) with
      the live `GraphState`; one logical request, latency = sink-node
      completion.

    Aggregates mirror the sliced-parent semantics ops already have: the
    handle completes when its last member/node does, and per-node
    results are addressed through the handle (``handle["o_proj"]``,
    `result_of`) exactly like a sliced parent's merged ``result``.
    """

    seq: int
    tenant: str
    request: Optional[GemmRequest]
    submit_t: float
    done_t: Optional[float] = None
    result: object = None           # jax.Array when executed
    plan: Optional[GroupPlan] = None
    deadline_t: float = math.inf    # submit_t + SLO p99 target (§17.2)
    rank: int = 1                   # tenant SLO rank at admission
    # Slicing linkage (§17.1): a sliced submit returns the *parent*
    # ticket; only the pieces enter the queues.  The parent completes
    # (and merges results) when its last piece does.
    parent: Optional["Ticket"] = field(default=None, repr=False)
    pieces: Optional[List["Ticket"]] = field(default=None, repr=False)
    merge_plan: Optional[SlicePlan] = field(default=None, repr=False)
    # Graph / aggregate linkage (§19.2).
    kind: str = "op"                # "op" | "node" | "bundle" | "graph"
    logical: bool = True            # counted in submitted/completed (§19.3)
    node: Optional[str] = None      # node name (kind == "node")
    graph: Optional["Ticket"] = field(default=None, repr=False)
    agg: Optional["Ticket"] = field(default=None, repr=False)
    members: Optional[List["Ticket"]] = field(default=None, repr=False)
    nodes: Optional[Dict[str, "Ticket"]] = field(default=None, repr=False)
    state: Optional[GraphState] = field(default=None, repr=False)
    # Host clock at submit (a graph node: at release), for the queue wait.
    host_t: float = field(default_factory=time.perf_counter, repr=False)

    @property
    def desc(self) -> GemmDesc:
        return self.request.desc

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.done_t is None else self.done_t - self.submit_t

    @property
    def sliced(self) -> bool:
        return self.pieces is not None

    # ------------------------------------------- aggregate views (§19.2)
    @property
    def done(self) -> bool:
        if self.state is not None:
            return self.state.done
        if self.members is not None:
            return all(m.done_t is not None for m in self.members)
        return self.done_t is not None

    def __getitem__(self, key) -> "Ticket":
        """Per-node (graph, by name) or per-member (bundle, by index)
        ticket — the uniform way callers reach constituent results."""
        if self.nodes is not None:
            return self.nodes[key]
        if self.members is not None:
            return self.members[key]
        raise TypeError(f"{self.kind!r} ticket has no constituents")

    def result_of(self, name: str):
        """Executed result of one graph node (None in shadow mode)."""
        return self[name].result

    def results(self) -> Dict[object, object]:
        """All constituent results keyed by node name (graph) or
        position (bundle); a plain op maps its own seq to its result."""
        if self.nodes is not None:
            return {n: t.result for n, t in self.nodes.items()}
        if self.members is not None:
            return {i: t.result for i, t in enumerate(self.members)}
        return {self.seq: self.result}


@dataclass
class Launch:
    """One bound group: a `GroupPlan` applied to live tickets."""

    plan: GroupPlan
    tickets: List[Ticket]
    class_key: str
    cache_hit: bool
    start_t: float = 0.0
    end_t: float = 0.0
    # §18.2 outcome: which rung completed the launch (None = planned)
    # and the modeled device time the failed attempts consumed.
    fallback: Optional[str] = None
    penalty_s: float = 0.0
    achieved_s: Optional[float] = None   # wall clock, when executed


class _ClassQueue:
    """One compatibility class's pending tickets, kept in canonical order
    *at admission* (bisect insertion on the `_canonical_order` tuple, ties
    resolved by arrival like the old per-flush stable sort).

    The plan-cache signature key list is maintained incrementally as a
    parallel array, so `flush()` never sorts and never rebuilds the
    canonical order — the structural half of the O(µs) fast path."""

    __slots__ = ("tickets", "keys", "_orders", "oldest_t", "min_deadline",
                 "max_weight")

    def __init__(self) -> None:
        self.tickets: List[Ticket] = []
        self.keys: List[str] = []          # desc keys, canonical order
        self._orders: List[tuple] = []     # bisect keys (no key= needed)
        self.oldest_t = float("inf")       # earliest pending submit time
        self.min_deadline = float("inf")   # earliest pending EDF deadline
        self.max_weight = 0.0              # heaviest pending tenant weight

    def add(self, ticket: Ticket, weight: float = 1.0) -> None:
        order = _canonical_order(ticket.desc)
        i = bisect.bisect_right(self._orders, order)
        self._orders.insert(i, order)
        self.tickets.insert(i, ticket)
        self.keys.insert(i, ticket.desc.key())
        if ticket.submit_t < self.oldest_t:
            self.oldest_t = ticket.submit_t
        if ticket.deadline_t < self.min_deadline:
            self.min_deadline = ticket.deadline_t
        if weight > self.max_weight:
            self.max_weight = weight

    def take_all(self) -> tuple[List[Ticket], tuple]:
        """Pop every ticket (already canonically sorted) + signature keys."""
        tickets, keys = self.tickets, tuple(self.keys)
        self.tickets, self.keys, self._orders = [], [], []
        self.oldest_t = float("inf")
        self.min_deadline = float("inf")
        self.max_weight = 0.0
        return tickets, keys

    def __len__(self) -> int:
        return len(self.tickets)


class Runtime:
    def __init__(
        self,
        controller: ConcurrencyController | None = None,
        config: RuntimeConfig | None = None,
        telemetry: Telemetry | None = None,
        clock=time.monotonic,
        fault_injector: FaultInjector | None = None,
    ):
        self.ctrl = controller or ConcurrencyController()
        self.config = config or RuntimeConfig()
        self.telemetry = telemetry or Telemetry()
        self.clock = clock
        # §18: chaos layer (None in production → the executor is the
        # plain module function, bitwise-identical dispatch) and the
        # per-(family, class, tile) circuit breaker.  Breaker time runs
        # on the modeled launch timeline, so quarantine/cooldown behave
        # identically in virtual-clock replay and live serving.
        self.fault_injector = fault_injector
        self._exec_fn = (fault_injector.wrap(execute_schedule)
                         if fault_injector is not None else execute_schedule)
        self.breaker = CircuitBreaker(
            strikes=self.config.quarantine_strikes,
            cooldown_s=self.config.quarantine_cooldown_s)
        self._quarantined_descs: Dict[Tuple[str, str, str], List[str]] = {}
        self.available = self.ctrl.max_cd
        # unscaled chip state, so set_mesh re-derives and never compounds
        self._chip_spec = self.ctrl.spec
        self._chip_lib = self.ctrl.lib
        self.mesh_resources = None
        self.device_free_t = 0.0
        self._queues: Dict[str, _ClassQueue] = {}
        self._rr: int = 0               # round-robin cursor over class order
        self._order: List[str] = []     # class keys in first-seen order
        self._plan_cache: "OrderedDict[Signature, Schedule]" = OrderedDict()
        self._seq = 0
        self._flush_id = 0
        # Calibration plumbing (DESIGN.md §16): representative descs per
        # compatibility class (so a drift-flagged class key can be turned
        # back into tunable descriptors) and the queued re-tunes that
        # `process_retunes` runs off the dispatch path.
        self._class_descs: Dict[str, Dict[str, GemmDesc]] = {}
        self._retune: List[Tuple[str, str]] = []
        # SLO state (§17): per-tenant objectives and the memoized
        # per-desc-key isolated-time estimates admission slicing reads —
        # steady-state admission touches the cost model ZERO times.
        self._slos: Dict[str, TenantSLO] = {}
        self._iso_cache: Dict[str, float] = {}

    # ---------------------------------------------------------- SLOs (§17)
    def set_tenant_slo(self, tenant: str, slo: TenantSLO) -> None:
        self._slos[tenant] = slo

    def tenant_slo(self, tenant: str) -> TenantSLO:
        return self._slos.get(tenant, DEFAULT_SLO)

    def _isolated_estimate(self, desc) -> float:
        """Memoized modeled isolated time for admission decisions."""
        key = desc.key()
        est = self._iso_cache.get(key)
        if est is None:
            est = isolated_time(desc, self.ctrl.lib.get(desc).isolated,
                                self.ctrl.spec)
            self._iso_cache[key] = est
        return est

    def _admission_parts(self, desc) -> int:
        """How many pieces admission should slice ``desc`` into (§17.2):
        1 (don't slice) unless slicing is on, the op is sliceable, and
        its modeled isolated time exceeds ``flush_budget_s *
        slice_budget_frac`` — then just enough pieces to bring each
        under the threshold, capped at ``max_slices``."""
        cfg = self.config
        if (not cfg.slicing or cfg.flush_budget_s is None
                or not getattr(desc, "can_slice", False)):
            return 1
        threshold = cfg.flush_budget_s * cfg.slice_budget_frac
        if threshold <= 0:
            return 1
        est = self._isolated_estimate(desc)
        if est <= threshold:
            return 1
        return min(math.ceil(est / threshold), cfg.max_slices)

    def _make_pieces(self, ticket: Ticket, plan: SlicePlan) -> List[Ticket]:
        """Build the piece tickets for a sliced parent: ordinary tickets
        carrying piece descs (and piece operands when the parent has
        them), deadline/rank inherited, back-linked for completion."""
        req = ticket.request
        if family_of(req.desc) == "gemm":
            operands = (req.a, req.b) if req.a is not None else None
        else:
            operands = req.inputs
        per_piece = (plan.split_operands(operands)
                     if operands is not None else [None] * plan.parts)
        pieces: List[Ticket] = []
        for pdesc, pops in zip(plan.pieces, per_piece):
            if family_of(pdesc) == "gemm":
                preq = GemmRequest(
                    desc=pdesc, tag=req.tag,
                    a=None if pops is None else pops[0],
                    b=None if pops is None else pops[1])
            else:
                preq = GemmRequest(desc=pdesc, tag=req.tag, inputs=pops)
            self._seq += 1
            pieces.append(Ticket(
                seq=self._seq, tenant=ticket.tenant, request=preq,
                submit_t=ticket.submit_t, deadline_t=ticket.deadline_t,
                rank=ticket.rank, parent=ticket))
        ticket.pieces = pieces
        ticket.merge_plan = plan
        self.telemetry.record_slices(ticket.tenant, plan.parts)
        return pieces

    # ------------------------------------------------------------- admit
    def submit(
        self,
        work,
        tenant: str = "default",
        now: float | None = None,
    ) -> Ticket:
        """THE submission surface (§19): one polymorphic entry point.

        - a single `GemmRequest`/OpDesc → per-class admission (§10), the
          classic ``"op"`` ticket;
        - a sequence of them → a heterogeneous bundle into the shared
          mixed-op queue (§14), returned as one ``"bundle"`` handle over
          per-member tickets;
        - an `OpGraph` → dataflow submission (§19.2): the ready frontier
          is released now, dependents release as predecessors complete,
          and one ``"graph"`` handle exposes per-node results by name.

        Always returns exactly one `Ticket`; callers never branch on the
        submission kind.  The historical names (`submit_bundle`,
        `integration.submit_decode_bundle`) survive as deprecation
        wrappers around this method.
        """
        t0 = time.perf_counter()
        with TraceAnnotation("runtime.submit"):
            if isinstance(work, OpGraph):
                ticket = self._submit_graph(work, tenant, now)
            elif isinstance(work, (list, tuple)):
                ticket = self._submit_bundle(work, tenant, now)
            else:
                ticket = self._submit_one(work, tenant, now)
        self.telemetry.record_host("submit", time.perf_counter() - t0)
        return ticket

    def _submit_one(
        self,
        request: GemmRequest | GemmDesc,
        tenant: str = "default",
        now: float | None = None,
    ) -> Ticket:
        if not isinstance(request, GemmRequest):
            request = GemmRequest(desc=request)
        now = self.clock() if now is None else now
        slo = self.tenant_slo(tenant)
        self._seq += 1
        ticket = Ticket(seq=self._seq, tenant=tenant, request=request,
                        submit_t=now, deadline_t=now + slo.p99_target_s,
                        rank=slo.rank)
        parts = self._admission_parts(request.desc)
        if parts > 1:
            # §17.2: oversized op — only the pieces enter the queues; the
            # caller holds the parent, which completes with its last piece.
            plan = slice_plan(request.desc, parts)
            for piece in self._make_pieces(ticket, plan):
                self._enqueue(piece, slo.weight)
        else:
            self._enqueue(ticket, slo.weight)   # canonical-position insert
        self.telemetry.record_submit()
        return ticket

    def _enqueue(self, ticket: Ticket, weight: float = 1.0,
                 class_key: str | None = None) -> None:
        key = class_key if class_key is not None else compat_key(ticket.desc)
        q = self._queues.get(key)
        if q is None:
            q = self._queues[key] = _ClassQueue()
            self._order.append(key)
        q.add(ticket, weight)

    def submit_bundle(
        self,
        requests: Sequence,
        tenant: str = "default",
        now: float | None = None,
    ) -> List[Ticket]:
        """Deprecated: use ``submit(sequence)`` (§19).  Returns the
        member tickets like the historical API did."""
        warnings.warn(
            "Runtime.submit_bundle is deprecated; use Runtime.submit() "
            "with a sequence (DESIGN.md §19)",
            DeprecationWarning, stacklevel=2)
        return list(self.submit(list(requests), tenant=tenant,
                                now=now).members)

    def _submit_bundle(
        self,
        requests: Sequence,
        tenant: str = "default",
        now: float | None = None,
    ) -> Ticket:
        """Admit a heterogeneous decode bundle for co-scheduling (§14).

        Unlike single-op admission, the ops are NOT split into
        per-family §6.7 class queues: they enter the shared mixed-bundle
        queue, and `flush` plans that queue through
        `ConcurrencyController.plan_mixed` — so a decode step's QKV
        GEMMs, attention, MoE grouped-GEMM, and scan become one (or a
        few) concurrent groups with the CD decided over the
        heterogeneous pool.  Same plan cache, same fast path: the bundle
        signature is canonical, so steady-state traffic replans nothing.

        Each member stays a *logical* request (per-member latency
        accounting, §14/§17 semantics unchanged); the returned
        ``"bundle"`` handle is an aggregate view that completes with its
        last member.
        """
        now = self.clock() if now is None else now
        slo = self.tenant_slo(tenant)
        q = self._queues.get(MIXED_CLASS)
        if q is None:
            q = self._queues[MIXED_CLASS] = _ClassQueue()
            self._order.append(MIXED_CLASS)
        members: List[Ticket] = []
        for request in requests:
            if not isinstance(request, GemmRequest):
                request = GemmRequest(desc=request)
            self._seq += 1
            ticket = Ticket(seq=self._seq, tenant=tenant, request=request,
                            submit_t=now, deadline_t=now + slo.p99_target_s,
                            rank=slo.rank)
            parts = self._admission_parts(request.desc)
            if parts > 1:
                plan = slice_plan(request.desc, parts)
                for piece in self._make_pieces(ticket, plan):
                    q.add(piece, slo.weight)
            else:
                q.add(ticket, slo.weight)
            self.telemetry.record_submit()
            members.append(ticket)
        self._seq += 1
        handle = Ticket(seq=self._seq, tenant=tenant, request=None,
                        submit_t=now, deadline_t=now + slo.p99_target_s,
                        rank=slo.rank, kind="bundle", logical=False,
                        members=members)
        for m in members:
            m.agg = handle
        return handle

    # ------------------------------------------------ graph admission (§19)
    def _submit_graph(
        self,
        graph: OpGraph,
        tenant: str = "default",
        now: float | None = None,
    ) -> Ticket:
        """Admit an `OpGraph` for dataflow execution (§19.2).

        Validates the graph, creates one node ticket per op (all held by
        the returned ``"graph"`` handle, addressable by node name), and
        releases the ready frontier (the roots) into the shared mixed-op
        queue.  Dependents are released by `_complete_node` as their
        predecessors complete — with the predecessors' (possibly
        fallback-rung, §18.2) outputs wired into their operand slots —
        so `plan_mixed` sees, at every flush, the union of ready nodes
        across all live graphs, bundles, and requests.

        The whole graph is ONE logical request (§19.3): `submitted`
        counts it once and its latency is sink-node completion, exactly
        parallel to a sliced parent's parent-once accounting.
        """
        now = self.clock() if now is None else now
        slo = self.tenant_slo(tenant)
        state = GraphState(graph)       # validates (cycles, slots, shapes)
        self._seq += 1
        handle = Ticket(seq=self._seq, tenant=tenant, request=None,
                        submit_t=now, deadline_t=now + slo.p99_target_s,
                        rank=slo.rank, kind="graph", logical=True,
                        nodes={}, state=state)
        for name in state.order:
            self._seq += 1
            tk = Ticket(seq=self._seq, tenant=tenant, request=None,
                        submit_t=now, deadline_t=handle.deadline_t,
                        rank=slo.rank, kind="node", logical=False,
                        node=name, graph=handle)
            state.tickets[name] = tk
            handle.nodes[name] = tk
        self.telemetry.record_submit()          # ONE logical request
        self.telemetry.record_graph_submit(len(state.order))
        for name in state.ready():
            self._release_node(handle, name, now)
        return handle

    def _release_node(self, handle: Ticket, name: str, now: float) -> None:
        """Move one ready graph node into the mixed-op queue: bind its
        request from the operand slots wired so far (`bind_operands`; a
        partially-known slot set stays a shadow request), stamp its
        submit time with the release time (so waiting-time/EDF ordering
        measures *readiness*, not graph admission), and admission-slice
        it exactly like a directly-submitted op (§17.2) — the sliced
        node completes through the ordinary parent-merge path before its
        dependents see the merged result."""
        state = handle.state
        state.mark_released(name)
        gnode = state.graph.nodes[name]
        tk = state.tickets[name]
        tk.submit_t = max(tk.submit_t, now)
        tk.host_t = time.perf_counter()
        tk.request = bind_operands(gnode.desc, state.operands_for(name),
                                   tag=gnode.tag or name)
        weight = self.tenant_slo(handle.tenant).weight
        parts = self._admission_parts(gnode.desc)
        if parts > 1:
            plan = slice_plan(gnode.desc, parts)
            for piece in self._make_pieces(tk, plan):
                self._enqueue(piece, weight, class_key=MIXED_CLASS)
        else:
            self._enqueue(tk, weight, class_key=MIXED_CLASS)

    def set_available(self, n: int) -> None:
        """Update live available parallelism (other streams/devices taking
        slots).  Part of the plan-cache key, so stale plans never re-bind."""
        self.available = max(1, int(n))

    def set_mesh(self, mesh):
        """Derate the runtime for a sharded mesh (DESIGN.md §12.5).

        Tensor-parallel shards co-resident on each chip shrink the VMEM /
        bandwidth a concurrent group can claim: the controller's cost
        model *and GO library* switch to the per-shard `TPUSpec.scaled`
        variant (tiles tuned for full-chip VMEM would be wrong under a
        shard's share), and the ``available`` slot cap drops to the
        per-shard budget, so CD_exec = min(CD_pred, available) sees
        post-sharding capacity.  Always derates from the chip spec/lib
        captured at construction — calling with a new mesh re-derives,
        never compounds — and a derated mesh gets a fresh private library
        (the process-global default stays chip-tuned); prewarm after
        set_mesh, not before."""
        from repro.core.library import GOLibrary
        from repro.dist.resources import mesh_resources

        res = mesh_resources(mesh, spec=self._chip_spec,
                             max_cd=self.ctrl.max_cd)
        self.ctrl.spec = res.spec
        self.ctrl.lib = (
            self._chip_lib if res.frac == 1.0 else GOLibrary(spec=res.spec)
        )
        # The controller's memoized CD/feature decisions were derived from
        # the previous spec+library — stale under the derated share.
        self.ctrl.invalidate_caches()
        self.set_available(res.slot_budget)
        self.invalidate_plans()
        self._iso_cache.clear()   # admission estimates were per-chip-spec
        self.mesh_resources = res
        return res

    def queue_depths(self) -> Dict[str, int]:
        return {k: len(q) for k, q in self._queues.items() if q}

    def pending(self) -> int:
        return sum(len(q) for q in self._queues.values())

    # ------------------------------------------------------------ prewarm
    def prewarm(self, work, plan: bool = True) -> int:
        """THE prewarm surface (§19): tune ahead of traffic and seed the
        plan cache, polymorphic like `submit`:

        - an `OpGraph` → tune every node desc and seed the mixed-queue
          signature of each topological wave (what successive flushes of
          a lone graph will plan);
        - a GEMM-only sequence → the classic catalog prewarm: tune all,
          seed each §6.7 class's all-at-once signature (this is a tuning
          *catalog*, e.g. every batch size a decode service may see, not
          a co-submitted bundle);
        - a sequence containing any non-GEMM family → a §14 decode
          bundle: tune all, seed the bundle's mixed-queue signature.
          (A GEMM-only bundle destined for `submit(sequence)` should be
          prewarmed as a single-wave `OpGraph` to seed its mixed
          signature.)

        Planning cost paid here is recorded as prewarm overhead (not as an
        online cache miss), so the live hit rate measures steady-state
        cache behaviour while `cp_overhead_paid_s` still accounts for
        every plan actually derived."""
        if isinstance(work, OpGraph):
            fresh = self.ctrl.lib.prewarm(work.descs())
            if plan:
                for wave in work.waves():
                    self._seed_mixed_plan(
                        [work.nodes[n].desc for n in wave])
            return fresh
        descs = list(work) if isinstance(work, (list, tuple)) else [work]
        if any(family_of(d) != "gemm" for d in descs):
            return self._prewarm_mixed(descs, plan)
        fresh = self.ctrl.lib.prewarm(descs)
        if plan and descs:
            for key in {compat_key(d) for d in descs}:
                members = [d for d in descs if compat_key(d) == key]
                _, hit = self._plan_for(self._canonical_sort(members))
                if not hit:
                    self.telemetry.record_prewarm_plan(CP_OVERHEAD_S)
        return fresh

    def prewarm_bundle(self, descs: Sequence) -> int:
        """Deprecated: use ``prewarm(sequence)`` / ``prewarm(graph)``
        (§19)."""
        warnings.warn(
            "Runtime.prewarm_bundle is deprecated; use Runtime.prewarm() "
            "(DESIGN.md §19)",
            DeprecationWarning, stacklevel=2)
        return self._prewarm_mixed(list(descs), plan=True)

    def _prewarm_mixed(self, descs: List, plan: bool = True) -> int:
        """Tune a heterogeneous bundle's ops ahead of traffic and seed the
        plan cache with its mixed-queue signature (§14), so the first
        live decode step is already a cache-hit flush."""
        fresh = self.ctrl.lib.prewarm(descs)
        if plan and descs:
            self._seed_mixed_plan(descs)
        return fresh

    def _seed_mixed_plan(self, descs: List) -> None:
        """Derive (and cache) the mixed-queue plan for one co-submitted
        desc set, billed as prewarm overhead."""
        members = self._canonical_sort(descs)
        _, hit = self._plan_for_keys(
            (MIXED_CLASS,) + tuple(d.key() for d in members),
            lambda: members, planner=self.ctrl.plan_mixed)
        if not hit:
            self.telemetry.record_prewarm_plan(CP_OVERHEAD_S)

    # -------------------------------------------------------------- flush
    def flush(
        self,
        now: float | None = None,
        force: bool = False,
    ) -> List[Launch]:
        """Serve every ripe compatibility class (head waited ≥ window_s).

        Round-robin (default): classes are visited starting after the
        last serviced class and their groups interleave into the launch
        order.  EDF (``config.policy="edf"``, §17.3): ripe classes are
        served earliest-deadline-first (weight breaks ties), launches
        are ordered by their members' earliest deadline, and a
        ``flush_budget_s`` binds only a prefix of that order — the rest
        requeue with their original deadlines, so a monolithic tenant's
        backlog yields the device at every flush boundary.

        Three phases, each a span and a `Telemetry.host_s` entry: ``plan``
        (ripe classes, their queues, the plan-cache probe or planning),
        ``launch`` (the budget cut, binding to the timeline, execution)
        and ``record`` (group records and calibration).  A ticket's queue
        wait runs from its submit to the end of ``plan``.
        """
        now = self.clock() if now is None else now
        evals0 = EVAL_COUNTER.evals
        resorts0 = self.telemetry.sig_resorts
        t0 = time.perf_counter()
        with TraceAnnotation("runtime.plan"):
            ripe = [
                k for k in self._order
                if self._queues.get(k)
                and (force or now - self._queues[k].oldest_t >= self.config.window_s)
            ]
            if not ripe:
                self.telemetry.record_host("plan", time.perf_counter() - t0)
                return []
            self._flush_id += 1
            self.telemetry.record_flush(self.queue_depths())

            edf = self.config.policy == "edf"
            if edf:
                # Earliest-deadline class first; deadlines are absolute, so a
                # waiting class only rises in this order — no starvation.
                rotated = sorted(ripe, key=lambda k: (
                    self._queues[k].min_deadline, -self._queues[k].max_weight, k))
            else:
                # Rotate so each flush starts service at a different class
                # (fairness).
                start = self._rr % max(len(self._order), 1)
                rotated = [k for k in self._order[start:] + self._order[:start]
                           if k in ripe]
                self._rr = (self._order.index(rotated[0]) + 1) % len(self._order)

            per_class: List[List[Launch]] = []
            planning_s = 0.0
            for key in rotated:
                # Tickets come back already canonically ordered and the
                # signature keys are maintained incrementally — no sort, no
                # per-flush signature rebuild (telemetry.sig_resorts counts
                # any future regression to a full re-sort).
                tickets, sig_keys = self._queues[key].take_all()
                if key == MIXED_CLASS:
                    # Ready-set depth (§19.3): how many graph nodes this
                    # concurrency window could draw from — the dataflow
                    # executor's analogue of queue depth.
                    depth = sum(1 for t in tickets
                                if t.kind == "node" or
                                (t.parent is not None
                                 and t.parent.kind == "node"))
                    if depth:
                        self.telemetry.record_ready_depth(depth)
                    ranks = [t.rank for t in tickets] if edf else None
                    if ranks is not None and len(set(ranks)) > 1:
                        # Rank-aware chunking changes the plan, so the rank
                        # pattern joins the signature; tenant ranks are
                        # static, so steady-state traffic still hits.
                        sched, hit = self._plan_for_keys(
                            (MIXED_CLASS,) + sig_keys
                            + ("ranks:" + "".join(map(str, ranks)),),
                            lambda: [t.desc for t in tickets],
                            planner=lambda descs, available: self.ctrl.plan_mixed(
                                descs, available=available, ranks=ranks))
                    else:
                        sched, hit = self._plan_for_keys(
                            (MIXED_CLASS,) + sig_keys,
                            lambda: [t.desc for t in tickets],
                            planner=self.ctrl.plan_mixed)
                else:
                    sched, hit = self._plan_for_keys(
                        sig_keys, lambda: [t.desc for t in tickets])
                self.telemetry.record_plan(hit, CP_OVERHEAD_S)
                if not hit:
                    planning_s += CP_OVERHEAD_S
                per_class.append([
                    Launch(plan=gp, tickets=[tickets[i] for i in gp.indices],
                           class_key=key, cache_hit=hit)
                    for gp in sched.groups
                ])

            if edf:
                launches = [ln for groups in per_class for ln in groups]
                launches.sort(key=lambda ln: (
                    min(tk.deadline_t for tk in ln.tickets),
                    -max(self.tenant_slo(tk.tenant).weight for tk in ln.tickets),
                    min(tk.seq for tk in ln.tickets)))
            else:
                launches = _interleave(per_class)
        bound = time.perf_counter()
        with TraceAnnotation("runtime.launch"):
            # Budgeted (preemptible) flush §17.3: the budget is a COMMIT
            # HORIZON — a flush may bind launches only until the modeled
            # device is committed through ``now + flush_budget_s``.  Work
            # past the horizon requeues (deadlines intact), so later
            # flushes re-order it against whatever arrived meanwhile: this
            # is what keeps a sliced prefill preemptible instead of merely
            # chopped.  If the device is already committed past the horizon
            # nothing binds this flush; otherwise at least one launch does
            # (even one that overshoots), so forced flushing makes progress.
            base = max(self.device_free_t, now + planning_s)
            budget = self.config.flush_budget_s
            if budget is not None:
                horizon = now + budget
                acc, cut = base, 0
                for launch in launches:
                    if cut == 0:
                        # Only prior *committed* work blocks the first launch;
                        # planning overhead may overshoot (a forced flush on an
                        # idle device must always make progress, or drain spins).
                        if self.device_free_t > horizon:
                            break
                    elif acc + _launch_cost(launch) > horizon:
                        break
                    acc += _launch_cost(launch)
                    cut += 1
                if cut < len(launches):
                    for launch in launches[cut:]:
                        self._requeue(launch)
                    self.telemetry.record_deferred(len(launches) - cut)
                    launches = launches[:cut]

            # Modeled single-device timeline; real execution optionally rides it.
            # Planning cost (cache misses) is hidden behind prior kernels when
            # the device is busy (§6.5) but delays dispatch when it is idle —
            # this is where the plan cache buys measurable latency.
            t = base
            oldest = bound
            submitted, waits = 0.0, 0
            for launch in launches:
                launch.start_t = t
                if self.config.execute:
                    launch.achieved_s = self._execute(launch)
                # Fallback attempts consume modeled device time too (§18.2):
                # `penalty_s` stays 0.0 whenever the planned schedule
                # succeeded, so the healthy timeline is bitwise-identical to
                # the unhardened one.
                t += _launch_cost(launch) + launch.penalty_s
                launch.end_t = t
                waits += len(launch.tickets)
                for ticket in launch.tickets:
                    ticket.done_t = launch.end_t
                    ticket.plan = launch.plan
                    self._finish(ticket)
                    host_t = ticket.host_t
                    submitted += host_t
                    if host_t < oldest:
                        oldest = host_t
            if launches:
                self.device_free_t = t
        t2 = time.perf_counter()
        with TraceAnnotation("runtime.record"):
            for launch in launches:
                # §6.11 fusion happens before admission (one wide request with a
                # "-fused" tag); surface it in telemetry instead of "single".
                mode = launch.plan.mode
                if mode == "single" and launch.tickets[0].request.tag.endswith("-fused"):
                    mode = "fused"
                self.telemetry.record_group(GroupRecord(
                    flush_id=self._flush_id,
                    class_key=launch.class_key,
                    tenants=[tk.tenant for tk in launch.tickets],
                    cd=launch.plan.cd,
                    mode=mode,
                    modeled_time_s=launch.plan.modeled_time_s,
                    achieved_time_s=launch.achieved_s,
                    cache_hit=launch.cache_hit,
                    fallback=launch.fallback,
                    graph_ids=_graph_ids(launch.tickets),
                    tiles=tuple(t.key() for t in
                                (launch.plan.tiles or [launch.plan.tile])),
                ))
                self._feed_calibration(launch, launch.achieved_s)
            self._queue_stale_retunes()
            self.telemetry.record_flush_fastpath(
                EVAL_COUNTER.evals - evals0,
                self.telemetry.sig_resorts - resorts0,
            )
        self.telemetry.record_flush_host(
            bound - t0, t2 - bound, time.perf_counter() - t2,
            waits, waits * bound - submitted, bound - oldest)
        return launches

    def drain(self, now: float | None = None) -> List[Launch]:
        """Force-flush until every queue is empty.  Under a flush budget
        a flush can bind nothing (device committed past the horizon), so
        drain advances its virtual clock to the commit edge and retries —
        exactly what a live dispatcher polling on ticks would observe."""
        out: List[Launch] = []
        cur = self.clock() if now is None else now
        while self.pending():
            got = self.flush(now=cur, force=True)
            out += got
            if not got:
                cur = max(cur, self.device_free_t)
        return out

    # ------------------------------------------------- completion (§17.1)
    def _finish(self, ticket: Ticket) -> None:
        """Sliced-parent completion, then logical completion: a parent
        is done when its last piece is; its result is the merge recipe
        applied to the piece results (when executing).  The completed
        ticket (piece-merged parent or plain op) then flows through
        `_complete_logical` — latency accounting for logical requests,
        dataflow propagation for graph nodes."""
        parent = ticket.parent
        if parent is None:
            self._complete_logical(ticket)
            return
        if any(p.done_t is None for p in parent.pieces):
            return
        parent.done_t = max(p.done_t for p in parent.pieces)
        parent.plan = ticket.plan
        if all(p.result is not None for p in parent.pieces):
            parent.result = parent.merge_plan.merge(
                [p.result for p in parent.pieces])
        self._complete_logical(parent)

    def _complete_logical(self, ticket: Ticket) -> None:
        """One whole op finished (merged if it was sliced).  Graph nodes
        propagate completion through their graph instead of recording a
        latency of their own (§19.3); bundle members additionally stamp
        their aggregate handle when they are the last one out."""
        if ticket.kind == "node":
            self._complete_node(ticket)
            return
        self.telemetry.record_latency(ticket.tenant, ticket.latency_s)
        agg = ticket.agg
        if (agg is not None and agg.done_t is None
                and all(m.done_t is not None for m in agg.members)):
            agg.done_t = max(m.done_t for m in agg.members)

    def _complete_node(self, tk: Ticket) -> None:
        """Dataflow propagation (§19.2): wire this node's output (which
        is whatever the fallback ladder produced, §18.2 — dependents
        must see fallback-rung outputs) into its dependents' operand
        slots, release the newly-ready ones into the mixed queue at the
        completion time, and complete the graph handle once its last
        node is done.  Released dependents enter fresh queues, so they
        are planned by the NEXT flush — on the modeled timeline they
        become available exactly when their producer finished."""
        handle = tk.graph
        state = handle.state
        for name in state.complete(tk.node, tk.result):
            self._release_node(handle, name, tk.done_t)
        if state.done:
            handle.done_t = max(t.done_t for t in handle.nodes.values())
            handle.plan = tk.plan
            self.telemetry.record_latency(handle.tenant, handle.latency_s)
            self.telemetry.record_graph_complete()

    def _requeue(self, launch: Launch) -> None:
        """Return a deferred launch's tickets to their class queue with
        submit time and deadline intact — deferral only makes them more
        urgent relative to fresh arrivals (the no-starvation invariant)."""
        for tk in launch.tickets:
            self._enqueue(tk, self.tenant_slo(tk.tenant).weight,
                          class_key=launch.class_key)

    # -------------------------------------------------- calibration (§16)
    def _feed_calibration(self, launch: Launch, achieved: Optional[float]):
        """Fold one executed launch's modeled-vs-achieved ratio into the
        controller's `CostCalibrator` — homogeneous class launches only
        (a mixed group's wall clock cannot be attributed to one class;
        its members' classes learn from their own per-class launches).
        Pure arithmetic: no cost-model evals, so the zero-eval flush
        fast-path gate is untouched."""
        cal = self.ctrl.calibrator
        if cal is None or launch.class_key == MIXED_CLASS:
            return
        descs = self._class_descs.setdefault(launch.class_key, {})
        for tk in launch.tickets:
            if len(descs) >= 4 and tk.desc.key() not in descs:
                continue
            descs[tk.desc.key()] = tk.desc
        if achieved is None or launch.fallback is not None:
            # A fallback launch's wall clock timed the whole ladder, not
            # the planned kernel — feeding it would teach the calibrator
            # that healthy plans are slow (§18.2).  (`cal.update` also
            # rejects non-finite times as a second line of defense.)
            return
        cal.update(family_of(launch.tickets[0].desc), launch.class_key,
                   launch.plan.modeled_time_s, achieved)

    def _queue_stale_retunes(self) -> None:
        """Drift detection → re-tune queue: classes whose |log ratio|
        EWMA crossed the calibrator's threshold are queued ONCE per
        excursion (`pop_stale` resets the drift state) for
        `process_retunes` to handle off the dispatch path."""
        cal = self.ctrl.calibrator
        if cal is None:
            return
        for fam_ck in cal.pop_stale():
            if fam_ck not in self._retune:
                self._retune.append(fam_ck)

    def pending_retunes(self) -> int:
        return len(self._retune)

    def process_retunes(self, now: float | None = None) -> int:
        """Run the queued drift re-tunes (the "background" half of §16 —
        callers invoke this between traffic, never inside flush):
        invalidate the stale classes' library entries, re-tune them in
        one `GOLibrary.prewarm` sweep, and drop every plan/memo derived
        from the stale entries.  Returns the number of re-tuned
        entries.

        Also the half-open probe point (§18.3): quarantines whose
        cooldown elapsed by ``now`` (modeled-timeline seconds; defaults
        to the wall clock) are released — the banned tile re-enters the
        tuner's candidate set and one more failure re-quarantines it
        immediately, while a success clears the breaker."""
        fresh = 0
        if self._retune:
            descs: Dict[str, GemmDesc] = {}
            for _, ck in self._retune:
                descs.update(self._class_descs.get(ck, {}))
            self._retune.clear()
            if descs:
                self.ctrl.lib.invalidate(list(descs))
                fresh = self.ctrl.lib.prewarm(list(descs.values()))
                self.ctrl.invalidate_caches()
                self.invalidate_plans()
                self._iso_cache.clear()
        if self.breaker.active:
            now = self.clock() if now is None else now
            for key in self.breaker.release_due(now):
                keys = self._quarantined_descs.pop(key, [])
                _family, _class_key, tile_key = key
                self.ctrl.lib.release(keys, tile_key)
                if keys:
                    self.ctrl.lib.invalidate(keys)
                self.ctrl.invalidate_caches()
                self.invalidate_plans()
                self._iso_cache.clear()
                self.telemetry.record_probe()
        return fresh

    # ---------------------------------------------------------- internals
    def _plan_for_keys(
        self, keys: tuple, descs_fn, planner=None,
    ) -> tuple[Schedule, bool]:
        """Plan-cache probe on a prebuilt canonical key tuple; ``descs_fn``
        materializes the descriptors only on a miss, so a hit touches
        neither the planner nor the cost model.  ``planner`` overrides the
        per-class planner (the mixed-bundle queue plans via
        `plan_mixed`)."""
        sig: Signature = (keys, self.available)
        cached = self._plan_cache.get(sig)
        if cached is not None:
            self._plan_cache.move_to_end(sig)
            return cached, True
        plan = planner if planner is not None else self.ctrl.plan
        sched = plan(descs_fn(), available=self.available)
        self._plan_cache[sig] = sched
        while len(self._plan_cache) > self.config.plan_cache_capacity:
            self._plan_cache.popitem(last=False)
        return sched, False

    def _canonical_sort(self, descs: Sequence[GemmDesc]) -> List[GemmDesc]:
        """Full canonical-order sort of an arbitrary desc list — the slow
        path for planning entries that did NOT come through an
        admission-sorted class queue (offline prewarm today).  Every use
        is metered: flush() asserts its own delta stays zero."""
        self.telemetry.record_sig_resort()
        return sorted(descs, key=_canonical_order)

    def _plan_for(self, descs: Sequence[GemmDesc]) -> tuple[Schedule, bool]:
        """Plan a desc list already in canonical order (`_canonical_sort`
        for arbitrary lists)."""
        return self._plan_for_keys(
            tuple(d.key() for d in descs), lambda: descs)

    def _execute(self, launch: Launch) -> Optional[float]:
        reqs = [t.request for t in launch.tickets]

        def has_operands(r) -> bool:
            if family_of(r.desc) == "gemm":
                return r.a is not None and r.b is not None
            return r.inputs is not None

        if any(not has_operands(r) for r in reqs):
            return None
        if any(getattr(r.desc, "batch", 1) != 1 for r in reqs):
            # B-GEMMs (§6.7) are modeled but have no grouped execute path
            # in the kernels yet — stay in shadow (modeled-only) mode.
            return None
        mini = Schedule(groups=[replace(
            launch.plan, indices=list(range(len(reqs))))])
        t0 = time.perf_counter()
        outs = self._execute_resilient(reqs, mini, launch)
        achieved = time.perf_counter() - t0
        for ticket, out in zip(launch.tickets, outs):
            ticket.result = out
        return achieved

    # -------------------------------------------- fallback ladder (§18.2)
    def _execute_resilient(self, reqs, mini: Schedule, launch: Launch):
        """Run one bound launch down the fallback ladder until it
        completes: planned schedule → ``max_retries`` same-plan retries
        → the group re-planned on the legacy/isolated tiles → sequential
        per-op reference execution (``force_ref``, never injected, no
        finiteness veto — it IS the correctness oracle).  Every failed
        attempt strikes the (family, class, tile) triples it used; the
        K-th consecutive strike quarantines the GO entry (§18.3).  Each
        failed attempt charges one ``modeled_time_s`` of penalty onto
        the launch's modeled timeline."""
        plan = launch.plan
        n = len(reqs)
        planned_tiles = (plan.tiles if plan.mode == "mixed" and plan.tiles
                         else [plan.tile] * n)

        def legacy() -> tuple[Schedule, List]:
            iso = [self.ctrl.lib.get(r.desc).isolated for r in reqs]
            gp = replace(
                plan, indices=list(range(n)), tile=iso[0],
                tiles=iso if plan.mode == "mixed" else None)
            return Schedule(groups=[gp]), iso

        def reference() -> Schedule:
            return Schedule(groups=[
                GroupPlan(indices=[i], cd=1, tile=plan.tile, mode="single",
                          modeled_time_s=0.0)
                for i in range(n)])

        rungs = (["planned"]
                 + ["retry"] * max(0, int(self.config.max_retries))
                 + ["legacy", "reference"])
        failures = 0
        for rung in rungs:
            if rung in ("planned", "retry"):
                sched, tiles, force_ref = mini, planned_tiles, False
            elif rung == "legacy":
                sched, tiles = legacy()
                force_ref = False
            else:
                sched, tiles, force_ref = reference(), None, True
            try:
                outs = self._attempt(reqs, sched, force_ref)
            except Exception as exc:  # noqa: BLE001 — the ladder IS the handler
                self.telemetry.record_fault(fault_kind(exc))
                failures += 1
                if tiles is not None:
                    self._strike(reqs, tiles, now=launch.start_t)
                if rung == "reference":
                    # Nothing left to degrade to — a reference failure is
                    # a genuine bug, not a bad GO pick.  Surface it.
                    raise
                continue
            if rung != "planned":
                launch.fallback = rung
                launch.penalty_s = failures * plan.modeled_time_s
                self.telemetry.record_fallback(rung)
            elif self.breaker.active:
                # Healthy launch on a watched tile: consecutive-failure
                # counters reset (guarded so the no-fault path does zero
                # extra work).
                for r, tile in zip(reqs, planned_tiles):
                    self.breaker.succeed(family_of(r.desc),
                                         compat_key(r.desc), tile.key())
            return outs
        raise AssertionError("unreachable: reference rung returns or raises")

    def _attempt(self, reqs, sched: Schedule, force_ref: bool):
        """One ladder attempt: execute (through the chaos wrapper when
        injecting), synchronize, and veto non-finite outputs — except on
        the reference rung, whose numerics are trusted by definition."""
        outs = self._exec_fn(reqs, sched, interpret=self.config.interpret,
                             force_ref=force_ref)
        for o in outs:
            o.block_until_ready()
        if not force_ref:
            for o in outs:
                if not bool(jnp.isfinite(o).all()):
                    raise NonFiniteOutput("launch produced non-finite output")
        return outs

    def _strike(self, reqs, tiles, now: float) -> None:
        """Charge one failed attempt to every distinct (family, class,
        tile) it used; quarantine the ones that hit K strikes."""
        targets: Dict[Tuple[str, str, str], set] = {}
        for r, tile in zip(reqs, tiles):
            key = (family_of(r.desc), compat_key(r.desc), tile.key())
            targets.setdefault(key, set()).add(r.desc.key())
        for (fam, ck, tk), desc_keys in targets.items():
            if self.breaker.strike(fam, ck, tk, now):
                self._quarantine_entry(fam, ck, tk, desc_keys)

    def _quarantine_entry(self, family: str, class_key: str, tile_key: str,
                          desc_keys) -> None:
        """K-th strike side effects (§18.3), run exactly once per
        quarantine: ban the tile in the library, drop the tuned entries
        (the re-tune sees the ban), evict every cached plan that
        resolved to the tile, and clear the controller/admission memos
        derived from the now-stale entries."""
        keys = sorted(desc_keys)
        self._quarantined_descs[(family, class_key, tile_key)] = keys
        self.ctrl.lib.quarantine(keys, tile_key)
        self.ctrl.lib.invalidate(keys)
        evicted = self._evict_plans_using(tile_key)
        self.ctrl.invalidate_caches()
        self._iso_cache.clear()
        self.telemetry.record_quarantine(evicted_plans=evicted)

    def _evict_plans_using(self, tile_key: str) -> int:
        """Plan-cache hygiene (§18.3): drop every cached schedule that
        resolved any group (or mixed-group member) to ``tile_key`` — a
        poisoned plan must not be replayable from a cache hit.  Same
        invalidation contract as `set_mesh`, scoped to one tile."""
        doomed = [
            sig for sig, sched in self._plan_cache.items()
            if any(
                gp.tile.key() == tile_key
                or (gp.tiles is not None
                    and any(t.key() == tile_key for t in gp.tiles))
                for gp in sched.groups)
        ]
        for sig in doomed:
            del self._plan_cache[sig]
        return len(doomed)

    def invalidate_plans(self) -> None:
        self._plan_cache.clear()

    @property
    def plan_cache_size(self) -> int:
        return len(self._plan_cache)


def _graph_ids(tickets: List[Ticket]) -> Tuple[int, ...]:
    """Distinct graph-handle seqs a launch's members belong to (pieces
    resolve through their sliced parent) — ≥2 means the concurrency
    window genuinely mixed nodes from different graphs/requests (§19.3)."""
    ids = set()
    for tk in tickets:
        owner = tk.parent if tk.parent is not None else tk
        if owner.graph is not None:
            ids.add(owner.graph.seq)
    return tuple(sorted(ids))


def _canonical_order(d: GemmDesc) -> tuple:
    """Stable within-class ordering (largest M first) so equal queue
    contents produce equal signatures regardless of arrival order."""
    return (-d.M, d.key())


def _launch_cost(launch: Launch) -> float:
    """Modeled device time of one launch, including the per-piece slice
    overhead charge (`cost_model.SLICE_OVERHEAD_S`, §17.1)."""
    sliced = sum(1 for tk in launch.tickets if tk.parent is not None)
    return launch.plan.modeled_time_s + sliced * SLICE_OVERHEAD_S


def _interleave(per_class: List[List[Launch]]) -> List[Launch]:
    """Round-robin merge: class A group 1, class B group 1, …, A2, B2, …"""
    out: List[Launch] = []
    i = 0
    while True:
        row = [groups[i] for groups in per_class if i < len(groups)]
        if not row:
            return out
        out += row
        i += 1
