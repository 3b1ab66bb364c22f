"""Dynamic concurrency controller — the GPU command processor (CP) analogue
(paper §4.4), re-expressed for TPU dispatch (DESIGN.md §2).

At dispatch time the controller inspects the pending-GEMM queue (the
analogue of the CP reading kernel packets at queue heads), extracts the
features of the head GEMMs, runs the logistic predictor, and emits grouped
`pallas_call`s with the GO tile config for the chosen concurrency degree:

    CD_exec = min(CD_predicted, #available compatible GEMMs)

Heterogeneous queues follow §6.7: GEMMs are partitioned into compatibility
classes; two unique GEMMs execute fully-concurrently only if *both* prefer
that CD, otherwise they are split into homogeneous sub-groups.

The controller also implements the fusion-vs-concurrency policy (§6.11):
shared-input GEMMs (QKV) may be fused into one wide GEMM instead of grouped,
whichever the cost model favours.

`plan()` is pure logic (unit-testable, used by every benchmark); it is a
loop over `plan_group()`, which plans exactly ONE launch from the queue
head.  The online serving runtime (`repro.runtime`, DESIGN.md §10) plans
whole class queues via `plan(descs, available=...)` and memoizes the
resulting `Schedule`s; `execute_plan()` runs a precomputed `Schedule`
(e.g. a plan-cache hit) through the real kernels without re-planning,
while `execute()` is plan + execute in one call.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field, replace
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import (
    CostCalibrator,
    TPUSpec,
    device_spec,
    group_time,
    isolated_time,
    sequential_time,
)
from repro.core.gemm_desc import GemmDesc
from repro.core.library import GOLibrary, default_library
from repro.core.op_desc import family_of
from repro.core.predictor import CLASSES, Predictor, op_features
from repro.kernels.gemm.ops import TileConfig, gemm
from repro.kernels.grouped_gemm import grouped_gemm, ragged_gemm

# CP overhead (paper §5.4/§6.5): queue inspect + predict + packet rewrite.
CP_OVERHEAD_S = 8e-6


@dataclass
class GemmRequest:
    """One op ticket.  ``desc`` is any `OpDesc` (GEMMs carry operands in
    ``a``/``b``; non-GEMM families carry theirs in ``inputs``, in the
    positional order of the family op — see §14)."""

    desc: GemmDesc
    a: Optional[jax.Array] = None
    b: Optional[jax.Array] = None
    tag: str = ""
    inputs: Optional[tuple] = None


# Non-GEMM requests are the same record; the alias marks intent at call
# sites that submit heterogeneous ops.
OpRequest = GemmRequest


def bind_operands(desc, operands: Optional[tuple] = None,
                  tag: str = "") -> GemmRequest:
    """Build the family-correct request for ``desc`` from a positional
    operand tuple (`runtime.graph.FAMILY_SLOTS` order — what `_run_op`
    consumes): GEMMs unpack into ``a``/``b``, every other family keeps
    the tuple in ``inputs``.  ``operands=None`` is a shadow
    (modeled-only) request.  This is the single point where graph-edge
    wiring meets the executor's operand layout."""
    if family_of(desc) == "gemm":
        a, b = operands if operands is not None else (None, None)
        return GemmRequest(desc=desc, a=a, b=b, tag=tag)
    return GemmRequest(desc=desc, tag=tag, inputs=operands)


@dataclass
class GroupPlan:
    indices: List[int]            # queue positions executed in this launch
    cd: int                       # concurrency degree of the launch
    tile: TileConfig
    mode: str            # "grouped" | "ragged" | "single" | "fused" | "mixed"
    modeled_time_s: float
    # per-member tiles for heterogeneous ("mixed") launches, aligned with
    # ``indices``; None for single-tile modes.
    tiles: Optional[List[TileConfig]] = None


@dataclass
class Schedule:
    groups: List[GroupPlan] = field(default_factory=list)
    cp_overhead_s: float = 0.0

    @property
    def modeled_time_s(self) -> float:
        return sum(g.modeled_time_s for g in self.groups)


def _compatible(a, b) -> bool:
    """Groupable in one ragged launch: same K/N/transposes/dtype, any M.
    Only plain GEMMs qualify — other families pool with *identical*
    descriptors only (the `same` branch of `plan_group`)."""
    if not (isinstance(a, GemmDesc) and isinstance(b, GemmDesc)):
        return False
    return (
        a.N == b.N and a.K == b.K and a.ta == b.ta and a.tb == b.tb
        and a.dtype == b.dtype and a.batch == b.batch == 1
    )


@functools.lru_cache(maxsize=65536)
def compat_key(d) -> str:
    """Compatibility-class id: equal keys ⟺ plannable in one launch (§6.7).

    For plain GEMMs (batch == 1) equal keys coincide with `_compatible`.
    Batched GEMMs (§6.7 B-GEMM) class by their full key: they only pool
    with *identical* descriptors (the `same` branch of `plan_group`, which
    `_compatible` deliberately excludes).  Non-GEMM op families (§14)
    likewise class by their family-prefixed full key — classes never
    straddle families, so adding an op to a bundle cannot perturb the
    §6.7 class of its GEMM-only subset (property-tested in
    `tests/test_mixed_ops.py`).  Memoized (descriptors are frozen) so
    admission-time classification is a dict probe — part of the runtime's
    O(µs) dispatch path (DESIGN.md §10)."""
    if family_of(d) != "gemm":
        return d.key()
    if d.batch != 1:
        return d.key()
    return f"{d.N}_{d.K}_{int(d.ta)}{int(d.tb)}_{d.dtype}"


class ConcurrencyController:
    def __init__(
        self,
        library: GOLibrary | None = None,
        predictor: Predictor | None = None,
        spec: TPUSpec | None = None,
        max_cd: int = 16,
        go_tiles: bool = True,
        calibrator: CostCalibrator | None = None,
    ):
        # NB: `library or default_library()` would discard an *empty*
        # GOLibrary (its __len__ makes it falsy) — compare to None.
        self.lib = library if library is not None else default_library()
        self.predictor = predictor
        self.spec = device_spec() if spec is None else spec
        self.max_cd = max_cd
        # go_tiles=False plans grouped launches with the isolated-tuned tile
        # (the paper's "default" baseline; used by benchmark baselines).
        self.go_tiles = go_tiles
        # Optional self-calibration (DESIGN.md §16): modeled times are
        # multiplied by per-(family, compat-class) correction factors at
        # *selection* time only — plans keep the raw modeled time, so the
        # telemetry ratio that feeds the calibrator stays raw and the
        # loop is an EWMA, not an integrator.  ``None`` disables every
        # correction path bitwise (guarded by tests/test_calibration.py).
        self.calibrator = calibrator
        # Dispatch-path memos (DESIGN.md §10): CD decisions and feature
        # vectors per desc key.  MUST be invalidated when `lib`/`spec` are
        # swapped (Runtime.set_mesh does) — stale CDs would mis-plan.
        self._cd_cache: dict = {}
        self._feat_cache: dict = {}

    def invalidate_caches(self) -> None:
        """Drop memoized CD decisions / features (call after swapping the
        library, spec, or predictor — e.g. on mesh derating)."""
        self._cd_cache.clear()
        self._feat_cache.clear()
        if self.predictor is not None:
            self.predictor.invalidate_cache()

    # ------------------------------------------------------------ predict
    def _features(self, desc):
        key = desc.key()
        x = self._feat_cache.get(key)
        if x is None:
            x = op_features(desc, self.lib, self.spec)
            self._feat_cache[key] = x
        return x

    def preferred_cd(self, desc, available: int) -> int:
        if available <= 1:
            return 1
        floor = max(c for c in CLASSES if c <= available)
        ck = (desc.key(), floor)
        cached = self._cd_cache.get(ck)
        if cached is not None:
            return cached
        if self.predictor is not None:
            cd = self.predictor.predict_cd_one(
                desc.key(), lambda: self._features(desc), available)
        else:
            # Oracle fallback: modeled preferred CD from the GO library.
            cd = min(self.lib.get(desc).preferred_cd(), floor)
        self._cd_cache[ck] = cd
        return cd

    # -------------------------------------------------------- calibration
    def _group_factor(self, descs) -> float:
        """FLOPs-weighted geometric mean of the members' per-(family,
        compat-class) correction factors — the multiplier calibrated
        selection applies to a candidate group's modeled time.  A
        homogeneous group reduces to its class factor; 1.0 with no
        calibrator or no observations.  Within one class the factor is a
        common scale, so `preferred_cd`'s ordering is invariant — only
        cross-class comparisons (`plan_mixed` chunking, §6.11 fuse vs
        group) can change under correction."""
        cal = self.calibrator
        if cal is None:
            return 1.0
        num = den = 0.0
        for d in descs:
            f = cal.factor(family_of(d), compat_key(d))
            w = float(d.flops)
            if f != 1.0:
                num += w * math.log(f)
            den += w
        if num == 0.0 or den == 0.0:
            return 1.0
        return math.exp(num / den)

    def _corrected_schedule_time(self, sched: "Schedule", descs) -> float:
        """Calibrated total time of a schedule (selection metric only —
        stored plans keep raw modeled times)."""
        if self.calibrator is None:
            return sched.modeled_time_s
        return sum(
            g.modeled_time_s * self._group_factor(
                [descs[i] for i in g.indices])
            for g in sched.groups)

    # --------------------------------------------------------------- plan
    def plan_group(
        self,
        descs: Sequence[GemmDesc],
        pending: Sequence[int],
        available: int | None = None,
    ) -> tuple[GroupPlan, List[int]]:
        """Plan exactly ONE launch from the head of ``pending`` (§4.4).

        The per-dispatch unit of the dynamic logic: inspect the queue
        head, pool its compatible followers, predict CD, and emit one
        `GroupPlan`.  Returns the plan and the remaining pending indices.
        `plan()` is a loop over this.  ``available`` caps parallelism
        below ``max_cd`` — the serving runtime passes its live
        available-slot count through `plan()` here
        (CD_exec = min(CD_pred, avail)).
        """
        pending = list(pending)
        cap = self.max_cd if available is None else max(1, min(self.max_cd, available))
        head = descs[pending[0]]
        same = [i for i in pending if descs[i] == head]
        compat = [i for i in pending if _compatible(descs[i], head)]
        pool = same if len(same) >= len(compat) else compat
        hetero = pool is compat and len(compat) > len(same)

        cd = self.preferred_cd(head, available=min(len(pool), cap))
        if hetero:
            # §6.7: every unique member must prefer this CD, else split
            # into the homogeneous subset.
            uniq = {descs[i].key(): descs[i] for i in pool}
            if not all(
                self.preferred_cd(u, available=cd) >= cd
                for u in uniq.values()
            ):
                pool, hetero = same, False
                cd = self.preferred_cd(head, available=min(len(pool), cap))

        take = pool[: max(cd, 1)]
        cd_exec = len(take)
        entry = self.lib.get(head)
        tile = entry.tile_for_cd(cd_exec) if self.go_tiles else entry.isolated
        members = [(descs[i], tile) for i in take]
        if cd_exec == 1:
            mode = "single"
            t = isolated_time(head, self.lib.get(head).isolated, self.spec)
            tile = self.lib.get(head).isolated
        elif family_of(head) != "gemm":
            # A pool of identical non-GEMM ops is a concurrent group of
            # independent launches (no single fused kernel exists for
            # them) — plan it through the mixed path's per-member model.
            mode = "mixed"
            t = group_time(members, self.spec)
        else:
            mode = "ragged" if hetero else "grouped"
            t = group_time(members, self.spec)
        gp = GroupPlan(indices=take, cd=cd_exec, tile=tile, mode=mode,
                       modeled_time_s=t,
                       tiles=[tile] * cd_exec if mode == "mixed" else None)
        taken = set(take)
        return gp, [i for i in pending if i not in taken]

    def plan(
        self, descs: Sequence[GemmDesc], available: int | None = None
    ) -> Schedule:
        sched = Schedule(cp_overhead_s=CP_OVERHEAD_S)
        pending = list(range(len(descs)))
        while pending:
            gp, pending = self.plan_group(descs, pending, available=available)
            sched.groups.append(gp)
        return sched

    # ------------------------------------------------- mixed-family plan
    def plan_mixed(
        self, descs: Sequence, available: int | None = None,
        ranks: Sequence[int] | None = None,
    ) -> Schedule:
        """Co-schedule a heterogeneous decode bundle (§14).

        §6.7 pools only same-class GEMMs into one *launch*; a decode
        step's bundle is different — its QKV GEMMs, attention, MoE
        grouped-GEMM, and scan are distinct kernels that can run
        *concurrently* on resource shares (the ACS setting: concurrent
        heterogeneous, input-dependent kernels).  Per-class preferred-CD
        votes mislead here — a memory-bound scan that gains little from
        self-concurrency still fills a compute-bound GEMM's bandwidth
        bubbles — so the concurrency degree is chosen by evaluating the
        mixed pool directly under the cost model: every §5 class-size
        chunking of the bundle is modeled and the fastest wins
        (CD_exec = min(best chunk, available)).  The whole decision is
        plan-cached by the runtime, so steady-state bundles skip it
        entirely (DESIGN.md §10/§13).

        ``ranks`` (optional, one int per desc, lower = more urgent)
        stable-sorts the chunking order so same-rank ops keep their
        submission order but urgent ops land in the *earliest* chunks —
        the EDF hook (§17.3).  ``ranks=None`` is bitwise-identical to
        the pre-SLO planner."""
        sched = Schedule(cp_overhead_s=CP_OVERHEAD_S)
        n = len(descs)
        if n == 0:
            return sched
        cap = self.max_cd if available is None else max(
            1, min(self.max_cd, available))
        entries = [self.lib.get(d) for d in descs]
        if ranks is None:
            order = list(range(n))
        else:
            order = sorted(range(n), key=lambda i: ranks[i])

        def chunk_groups(size: int) -> List[GroupPlan]:
            groups = []
            for lo in range(0, n, size):
                take = order[lo:min(lo + size, n)]
                cd_exec = len(take)
                if cd_exec == 1:
                    i = take[0]
                    groups.append(GroupPlan(
                        indices=take, cd=1, tile=entries[i].isolated,
                        mode="single",
                        modeled_time_s=isolated_time(
                            descs[i], entries[i].isolated, self.spec)))
                    continue
                tiles = [
                    entries[i].tile_for_cd(cd_exec) if self.go_tiles
                    else entries[i].isolated
                    for i in take
                ]
                members = [(descs[i], t) for i, t in zip(take, tiles)]
                groups.append(GroupPlan(
                    indices=take, cd=cd_exec, tile=tiles[0], mode="mixed",
                    modeled_time_s=group_time(members, self.spec),
                    tiles=tiles))
            return groups

        sizes = sorted({c for c in CLASSES if c <= min(n, cap)} | {1}
                       | ({min(n, cap)} if min(n, cap) > 1 else set()))
        if self.calibrator is None:
            def chunk_time(gs: List[GroupPlan]) -> float:
                return sum(g.modeled_time_s for g in gs)
        else:
            # Calibrated selection (§16): rank chunkings by corrected
            # time; the winning plan still carries raw modeled times.
            def chunk_time(gs: List[GroupPlan]) -> float:
                return sum(
                    g.modeled_time_s * self._group_factor(
                        [descs[i] for i in g.indices])
                    for g in gs)
        best = min((chunk_groups(s) for s in sizes), key=chunk_time)
        sched.groups = best
        return sched

    # ---------------------------------------------------- fusion policy
    def plan_shared_input(
        self, descs: Sequence[GemmDesc]
    ) -> tuple[str, float, float]:
        """§6.11 QKV policy: GEMMs sharing A and K — fuse vs group.

        Returns (choice, fused_time, grouped_time) — the times are the
        raw modeled numbers; with a calibrator attached the *choice* is
        made on the corrected pair (the fused GEMM usually lives in a
        different compat class than the grouped members, so §16
        corrections can legitimately flip it)."""
        head = descs[0]
        fused_desc = replace(head, N=sum(d.N for d in descs))
        fused_tile = self.lib.get(fused_desc).isolated
        t_fused = isolated_time(fused_desc, fused_tile, self.spec)
        sched = self.plan(descs)
        t_group = sched.modeled_time_s
        if self.calibrator is None:
            choice = "fuse" if t_fused <= t_group else "group"
        else:
            fused_c = t_fused * self._group_factor([fused_desc])
            group_c = self._corrected_schedule_time(sched, descs)
            choice = "fuse" if fused_c <= group_c else "group"
        return (choice, t_fused, t_group)

    # ------------------------------------------------------------ execute
    def execute(
        self, requests: Sequence[GemmRequest], interpret: bool | None = None
    ) -> List[jax.Array]:
        descs = [r.desc for r in requests]
        sched = self.plan(descs)
        return self.execute_plan(requests, sched, interpret=interpret)

    def execute_plan(
        self,
        requests: Sequence[GemmRequest],
        sched: Schedule,
        interpret: bool | None = None,
        force_ref: bool = False,
    ) -> List[jax.Array]:
        """Run a precomputed `Schedule` through the real kernels.

        Separated from `execute()` so the serving runtime can replay a
        plan-cache hit without paying the planning pass again."""
        return execute_schedule(requests, sched, interpret=interpret,
                                force_ref=force_ref)


def execute_schedule(
    requests: Sequence[GemmRequest],
    sched: Schedule,
    interpret: bool | None = None,
    force_ref: bool = False,
) -> List[jax.Array]:
    """Run a `Schedule` through the real kernels — the controller-free
    execution core behind `ConcurrencyController.execute_plan`.  Module-
    level so the measurement harness (`core/measure.py`, DESIGN.md §16)
    times launches through the *same* family adapters and launch shapes
    the scheduler dispatches.

    ``force_ref=True`` pins every member to its family's XLA reference
    path — the trusted floor of the runtime's fallback ladder
    (DESIGN.md §18.2): no pallas, no GO tiles, numerics the reference
    implementations define."""
    outs: List[Optional[jax.Array]] = [None] * len(requests)
    for gp in sched.groups:
        reqs = [requests[i] for i in gp.indices]
        if gp.mode == "mixed":
            # Heterogeneous concurrent group: members are distinct
            # kernels; execute each through its family op at the
            # group's per-member GO tile (§14).  On real hardware
            # these dispatch concurrently; here correctness rides the
            # sequential member loop while latency is modeled.
            tiles = gp.tiles or [gp.tile] * len(gp.indices)
            for tile, i in zip(tiles, gp.indices):
                outs[i] = _run_op(requests[i], tile, interpret,
                                  force_ref=force_ref)
        elif gp.mode == "single" and family_of(reqs[0].desc) != "gemm":
            outs[gp.indices[0]] = _run_op(reqs[0], gp.tile, interpret,
                                          force_ref=force_ref)
        elif gp.mode == "single" or len(reqs) == 1:
            r = reqs[0]
            outs[gp.indices[0]] = gemm(
                r.a, r.b, ta=r.desc.ta, tb=r.desc.tb, tile=gp.tile,
                interpret=interpret, force_ref=force_ref,
            )
        elif gp.mode == "grouped":
            a = jnp.stack([_as_mk(r) for r in reqs])
            b = jnp.stack([_as_kn(r) for r in reqs])
            res = grouped_gemm(a, b, tile=gp.tile, interpret=interpret,
                               force_ref=force_ref)
            for j, i in enumerate(gp.indices):
                outs[i] = res[j]
        else:  # ragged
            bm = gp.tile.bm
            rows, sizes = [], []
            for r in reqs:
                m = _as_mk(r)
                pad = (-m.shape[0]) % bm
                if pad:
                    m = jnp.pad(m, ((0, pad), (0, 0)))
                rows.append(m)
                sizes.append(m.shape[0])
            a = jnp.concatenate(rows)
            b = jnp.stack([_as_kn(r) for r in reqs])
            res = ragged_gemm(
                a, b, jnp.asarray(sizes, jnp.int32), tile=gp.tile,
                interpret=interpret, force_ref=force_ref,
            )
            off = 0
            for j, i in enumerate(gp.indices):
                outs[i] = res[off : off + requests[i].desc.M]
                off += sizes[j]
    return outs  # type: ignore[return-value]


def _as_mk(r: GemmRequest) -> jax.Array:
    return r.a.T if r.desc.ta else r.a


def _as_kn(r: GemmRequest) -> jax.Array:
    return r.b.T if r.desc.tb else r.b


def _run_op(r: GemmRequest, tile: TileConfig, interpret: bool | None,
            force_ref: bool = False):
    """Execute one member of a mixed group through its family op (§14).

    Returns None when the request carries no operands (shadow dispatch).
    Family adapters live next to their kernels
    (`kernels/*/ops.py:*_for_desc`), imported lazily to keep module load
    GEMM-only for the common path."""
    fam = family_of(r.desc)
    if fam == "gemm":
        if r.a is None or r.b is None:
            return None
        return gemm(r.a, r.b, ta=r.desc.ta, tb=r.desc.tb, tile=tile,
                    interpret=interpret, force_ref=force_ref)
    if r.inputs is None:
        return None
    if fam == "flash_attention":
        from repro.kernels.flash_attention.ops import attention_for_desc

        return attention_for_desc(r.desc, *r.inputs, tile=tile,
                                  interpret=interpret, force_ref=force_ref)
    if fam == "grouped_gemm":
        from repro.kernels.grouped_gemm.ops import grouped_for_desc

        return grouped_for_desc(r.desc, *r.inputs, tile=tile,
                                interpret=interpret, force_ref=force_ref)
    if fam == "mamba_scan":
        from repro.kernels.mamba_scan.ops import scan_for_desc

        return scan_for_desc(r.desc, *r.inputs, tile=tile,
                             interpret=interpret, force_ref=force_ref)
    raise ValueError(f"unknown op family: {fam}")
