"""Calibrated analytical TPU cost model.

This is the measurement substrate for GOLDYLOC on a CPU-only container
(DESIGN.md §2, which also defines the GPU-resource → TPU-resource
mapping): kernel-grain latencies are derived from a three-term roofline
over the *tile config*, with explicit modeling of the two mechanisms the
paper shows drive concurrency behaviour:

1. **HBM traffic vs tile shape** — blocked matmul re-reads panels
   `tiles_n·M·K + tiles_m·K·N`; larger tiles ⇒ fewer re-reads (paper Fig. 4
   Kernel-3).  If a GEMM's A row-panel (bm·K) fits in its VMEM *share*, the
   kernel holds it resident and A is read once — losing residency when the
   share shrinks at higher CD reproduces the paper's large-K contention
   cliff (Fig. 5(b) ①).
2. **Pipeline occupancy vs waves** — a TPU core pipelines tiles over DMA;
   small GEMMs have fill/drain bubbles and per-launch overhead that
   grouping amortizes (paper's "fewer waves ⇒ better overlap").

**Split-K** (DESIGN.md §13) is a third, orthogonal axis: a kernel with
``split_k = s`` partitions the sequential K sweep into ``s`` independent
grid slices, each accumulating an f32 *partial* C that a reduce epilogue
sums.  The model charges the partials' extra HBM round-trip
(``2·s·M·N·4`` bytes) plus one extra launch, and credits the ``s×``
larger parallel tile count — which shrinks the per-tile fill/drain ramp,
the dominant cost for single-tile skinny GEMMs (decode-shape M≤mxu,
N≤bn), exactly the Stream-K tail-quantization recovery.

**Stream-K** (DESIGN.md §15) generalizes that to a *work-centric*
occupancy curve: ``stream_k = G`` runs a persistent grid of ``G``
workgroups, each walking an equal contiguous span of the global
``tm·tn·tk·batch`` MAC iterations.  The parallel instance count becomes
the live grid (flat work per workgroup, no tail-wave quantization term —
``n_tiles`` no longer quantizes on the output shape), and the only
added traffic is one extra f32 partial round-trip per output tile that
*straddles* a workgroup boundary — at most ``G - 1`` of them, computed
in closed form from the span period.  The fixup pass costs the same
extra launch as the split-K reduce epilogue.

**Evaluation layout** (DESIGN.md §13): the model is written once, in
NumPy, over struct-of-arrays (`DescBatch` × `TileBatch` × broadcastable
budget/bandwidth arrays).  The scalar functions (`kernel_stats`,
`isolated_time`, `group_time`, …) are thin wrappers over the same code
path, so batch and scalar evaluation are bitwise identical by
construction; `*_ref` pure-Python ports are kept as the parity oracle
and as the pre-vectorization baseline for `benchmarks/tuning.py`.
`EVAL_COUNTER` counts every (GEMM, tile, budget) evaluation so perf
regressions are count-detectable (flake-free in CI).

Times are in seconds.  Absolute values are estimates; the paper's metrics
are *ratios* (concurrent vs sequential), which are robust to the absolute
calibration.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Sequence

import jax
import numpy as np

from repro.core.gemm_desc import GemmDesc
from repro.core.op_desc import AttentionDesc, GroupedGemmDesc, ScanDesc, family_of
from repro.kernels.dispatch import VMEM_LIMIT_BYTES
from repro.kernels.gemm.ops import TileConfig


@dataclass(frozen=True)
class TPUSpec:
    """One chip's peaks (from `CHIP_SPECS`) plus the model's planning
    knobs."""

    name: str
    peak_flops_bf16: float
    peak_flops_fp32: float
    hbm_bw: float                    # B/s
    ici_bw: float                    # B/s of chip-to-chip interconnect
    vmem_bytes: int = VMEM_LIMIT_BYTES  # VMEM budget tiles are planned in
    launch_overhead_s: float = 3e-6  # kernel dispatch
    pipeline_fill_tiles: int = 2     # DMA double-buffer fill/drain depth
    mxu_dim: int = 128

    def peak(self, dtype: str) -> float:
        return self.peak_flops_fp32 if dtype == "f32" else self.peak_flops_bf16

    def scaled(self, frac: float) -> "TPUSpec":
        """Resource-constrained variant (the paper's GPU/2, GPU/4)."""
        return replace(
            self,
            name=f"{self.name}/{round(1 / frac)}" if frac != 1.0 else self.name,
            vmem_bytes=int(self.vmem_bytes * frac),
            hbm_bw=self.hbm_bw * frac,
        )


# Published per-chip peaks, keyed by `jax.Device.device_kind`.  Source:
# Google Cloud TPU documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.  The f32 peak is
# the model's assumption (half the bf16 MXU rate), not a published figure.
CHIP_SPECS = {
    "TPU v5 lite": TPUSpec(
        name="tpu-v5e",
        peak_flops_bf16=197e12,
        peak_flops_fp32=98.5e12,
        hbm_bw=819e9,
        ici_bw=1600e9 / 8,
    ),
}

# The planning target off-TPU (CPU runs and tests plan for a v5e).
DEFAULT_SPEC = CHIP_SPECS["TPU v5 lite"]


def device_spec(device=None) -> TPUSpec:
    """The spec of ``device`` (default: the process's first device).

    A TPU plans with its own published peaks; a TPU whose kind is not in
    `CHIP_SPECS` is an error, never a v5e stand-in.  Other platforms plan
    for `DEFAULT_SPEC`."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return DEFAULT_SPEC
    try:
        return CHIP_SPECS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no TPUSpec for device kind {device.device_kind!r}; add its "
            f"published peaks to CHIP_SPECS (known: {sorted(CHIP_SPECS)})"
        ) from None


RC_FRACTIONS = {"GPU": 1.0, "GPU/2": 0.5, "GPU/4": 0.25}

_STRIDED_DMA = 1 / 0.85  # paper Fig. 5(b) ③: strided operand loses ~15%


class EvalCounter:
    """Counts cost-model evaluations for count-based perf regression gates.

    ``evals`` is the number of (GEMM, tile, budget) tuples evaluated —
    one per element of a batched call; ``calls`` is the number of Python
    entries into the model (the per-call overhead the vectorized tuner
    amortizes).  `benchmarks/tuning.py` and the runtime fast-path tests
    assert on deltas of these.

    Counts are **per-thread** (thread-local storage): a delta taken
    around a code region (e.g. `Runtime.flush`) measures only that
    thread's evaluations, so a concurrent `GOLibrary` tune on another
    thread cannot fake a fast-path regression — and the unsynchronized
    `+=` never races.
    """

    __slots__ = ("_tls",)

    def __init__(self) -> None:
        import threading

        self._tls = threading.local()

    def _counts(self) -> list:
        c = getattr(self._tls, "counts", None)
        if c is None:
            c = self._tls.counts = [0, 0]
        return c

    @property
    def evals(self) -> int:
        return self._counts()[0]

    @property
    def calls(self) -> int:
        return self._counts()[1]

    def add(self, n: int) -> None:
        c = self._counts()
        c[0] += int(n)
        c[1] += 1

    def reset(self) -> None:
        self._tls.counts = [0, 0]

    def snapshot(self) -> tuple[int, int]:
        return tuple(self._counts())


EVAL_COUNTER = EvalCounter()


# --------------------------------------------------------- struct-of-arrays
@dataclass(frozen=True)
class TileBatch:
    """Struct-of-arrays over candidate `TileConfig`s (int64 fields).

    ``stream_k`` is optional (None ⇒ all-tile/split-K batch, the
    pre-Stream-K layout) so legacy constructions stay valid."""

    bm: np.ndarray
    bn: np.ndarray
    bk: np.ndarray
    split_k: np.ndarray
    stream_k: np.ndarray | None = None

    @staticmethod
    def from_tiles(tiles: Sequence[TileConfig]) -> "TileBatch":
        return TileBatch(
            bm=np.asarray([t.bm for t in tiles], np.int64),
            bn=np.asarray([t.bn for t in tiles], np.int64),
            bk=np.asarray([t.bk for t in tiles], np.int64),
            split_k=np.asarray([t.split_k for t in tiles], np.int64),
            stream_k=np.asarray([t.stream_k for t in tiles], np.int64),
        )

    def vmem_bytes(self, in_bytes: int = 2, acc_bytes: int = 4) -> np.ndarray:
        """Mirrors `TileConfig.vmem_bytes` (raw, unclamped dims)."""
        ab = 2 * (self.bm * self.bk + self.bk * self.bn) * in_bytes
        acc = self.bm * self.bn * acc_bytes
        out = self.bm * self.bn * in_bytes
        return ab + acc + out

    def tile(self, i: int) -> TileConfig:
        sk = 0 if self.stream_k is None else int(self.stream_k[i])
        return TileConfig(int(self.bm[i]), int(self.bn[i]), int(self.bk[i]),
                          int(self.split_k[i]), stream_k=sk)

    def __len__(self) -> int:
        return int(np.broadcast(self.bm, self.bn, self.bk, self.split_k).size)


@dataclass(frozen=True)
class DescBatch:
    """Struct-of-arrays over `GemmDesc`s (heterogeneous group members)."""

    M: np.ndarray
    N: np.ndarray
    K: np.ndarray
    batch: np.ndarray
    in_bytes: np.ndarray
    ta: np.ndarray
    tb: np.ndarray
    f32: np.ndarray

    @staticmethod
    def from_descs(descs: Sequence[GemmDesc]) -> "DescBatch":
        return DescBatch(
            M=np.asarray([d.M for d in descs], np.int64),
            N=np.asarray([d.N for d in descs], np.int64),
            K=np.asarray([d.K for d in descs], np.int64),
            batch=np.asarray([d.batch for d in descs], np.int64),
            in_bytes=np.asarray([d.in_bytes for d in descs], np.int64),
            ta=np.asarray([d.ta for d in descs], bool),
            tb=np.asarray([d.tb for d in descs], bool),
            f32=np.asarray([d.dtype == "f32" for d in descs], bool),
        )

    def peak(self, spec: TPUSpec) -> np.ndarray:
        return np.where(self.f32, spec.peak_flops_fp32, spec.peak_flops_bf16)


def _desc_fields(d):
    # GemmDesc and DescBatch expose the same field names (scalar vs array).
    return (d.M, d.N, d.K, d.batch, d.in_bytes, d.ta, d.tb)


def _peak_of(d, spec: TPUSpec):
    if isinstance(d, GemmDesc):
        return spec.peak(d.dtype)
    return d.peak(spec)


@dataclass(frozen=True)
class KernelStats:
    """Per-(GEMM, tile) features — the paper's #WGs / occupancy / #waves,
    re-expressed for TPU (DESIGN.md §2); consumed by the predictor's
    feature vector (DESIGN.md §4) and the tuner (DESIGN.md §3)."""

    n_tiles: int          # = #WGs (× split_k slices; = live grid stream-K)
    waves: float          # pipeline waves (tiles / in-flight slots)
    occupancy: float      # VMEM-utilization fraction of the budget used
    vmem_bytes: float     # working set (dbl-buffered panels + acc)
    hbm_bytes: float      # total traffic with panel-residency decision
    flops: float          # padded (includes tile-edge waste)
    mxu_util: float       # alignment efficiency
    a_resident: bool      # A row-panel held in VMEM (traffic saver)
    splits: int = 1       # effective split-K slice count (≤ k-tiles)
    streams: int = 0      # Stream-K live workgroup count (0 = not stream-K)


@dataclass(frozen=True)
class KernelStatsBatch:
    """`KernelStats` as broadcast NumPy arrays (one slot per evaluation)."""

    n_tiles: np.ndarray
    waves: np.ndarray
    occupancy: np.ndarray
    vmem_bytes: np.ndarray
    hbm_bytes: np.ndarray
    flops: np.ndarray
    mxu_util: np.ndarray
    a_resident: np.ndarray
    splits: np.ndarray
    streams: np.ndarray

    def item(self, i=()) -> KernelStats:
        return KernelStats(
            n_tiles=int(self.n_tiles[i]),
            waves=float(self.waves[i]),
            occupancy=float(self.occupancy[i]),
            vmem_bytes=float(self.vmem_bytes[i]),
            hbm_bytes=float(self.hbm_bytes[i]),
            flops=float(self.flops[i]),
            mxu_util=float(self.mxu_util[i]),
            a_resident=bool(self.a_resident[i]),
            splits=int(self.splits[i]),
            streams=int(self.streams[i]),
        )


# ------------------------------------------------------------- batched core
@dataclass(frozen=True)
class TilePrecomp:
    """Budget-independent tile math, factored out so repeated sweeps over
    the same (desc, tiles) pair with different budgets (the RC fractions in
    step ①, the CD shares in step ②) pay the tile arithmetic once."""

    tn: np.ndarray        # j-sweep length (A re-read factor)
    splits: np.ndarray    # effective split-K slice count (≤ k-tiles)
    streams: np.ndarray   # Stream-K live workgroup count (0 = not stream-K)
    n_tiles: np.ndarray   # parallel grid tiles (× splits; live grid stream-K)
    ws: np.ndarray        # per-instance working set
    a_panel: np.ndarray   # per-slice A row panel (bm · K/s · bytes)
    a_unit: np.ndarray    # one full A read: M·K·bytes·batch·stream
    bc_bytes: np.ndarray  # B + C + split-K partial traffic
    flops: np.ndarray     # padded FLOPs
    util: np.ndarray      # MXU alignment efficiency
    peak: np.ndarray      # dtype peak FLOP/s


def tile_precompute(d, t, spec: TPUSpec = DEFAULT_SPEC) -> TilePrecomp:
    M, N, K, batch, in_bytes, ta, tb = _desc_fields(d)
    mxu = spec.mxu_dim
    bm = np.minimum(t.bm, _round_up(M, mxu))
    bn = np.minimum(t.bn, _round_up(N, mxu))
    bk = np.minimum(t.bk, _round_up(K, mxu))
    tm, tn, tk = _cdiv(M, bm), _cdiv(N, bn), _cdiv(K, bk)
    # Split-K: s independent K-slices, each a parallel grid instance.
    s = np.minimum(t.split_k, tk)
    n_tiles = tm * tn * s * batch
    # Stream-K: a persistent grid of g_live workgroups, each walking
    # ⌈total/G⌉ of the tm·tn·tk·batch MAC iterations — the parallel
    # instance count IS the live grid (work-centric, no tail waves).
    sk = np.asarray(t.stream_k if getattr(t, "stream_k", None) is not None
                    else 0, np.int64)
    total = tm * tn * tk * batch
    ipw = _cdiv(total, np.maximum(np.minimum(sk, total), 1))
    g_live = _cdiv(total, ipw)
    n_tiles = np.where(sk > 0, g_live, n_tiles)
    streams = np.where(sk > 0, g_live, np.zeros_like(g_live))

    ws = (2 * (bm * bk + bk * bn) * in_bytes
          + bm * bn * 4 + bm * bn * in_bytes)
    # A row-panel: bm x (K / split) held in VMEM across the j sweep.
    a_panel = bm * K * in_bytes / s
    # Transposed storage streams with strided DMA — paper Fig. 5(b) ③'s
    # layout effect; v5e DMA loses ~15% on the strided operand.
    if isinstance(d, GemmDesc):
        a_stream = _STRIDED_DMA if ta else 1.0
        b_stream = _STRIDED_DMA if tb else 1.0
    else:
        a_stream = np.where(ta, _STRIDED_DMA, 1.0)
        b_stream = np.where(tb, _STRIDED_DMA, 1.0)
    a_unit = M * K * in_bytes * batch * a_stream
    b_bytes = tm * (K * N * in_bytes * batch) * b_stream
    c_bytes = M * N * in_bytes * batch
    # Split-K epilogue traffic: each slice writes an f32 partial C and the
    # reduce reads them all back (2·s·M·N·4); zero when un-split.
    part_bytes = np.where(s > 1, s * (2 * (M * N * 4) * batch), 0.0)
    # Stream-K partials: only output tiles *straddling* a workgroup
    # boundary pay the f32 partial round-trip — one straddle per interior
    # boundary that does not land exactly on a tile edge (closed form via
    # the span period; ≤ g_live − 1 total).
    period = tk // np.gcd(ipw, tk)
    straddle = (g_live - 1) - (g_live - 1) // period
    part_bytes = np.where(sk > 0, straddle * (2.0 * (bm * bn * 4)),
                          part_bytes)
    bc_bytes = (b_bytes + c_bytes) + part_bytes

    # padded FLOPs (tile-edge waste)
    flops = 2.0 * (tm * bm) * (tn * bn) * (tk * bk) * batch
    util = (
        _align_eff(bm, mxu)
        * _align_eff(bn, mxu)
        * _align_eff(bk, mxu)
    )
    return TilePrecomp(
        tn=tn, splits=s, streams=streams, n_tiles=n_tiles, ws=ws,
        a_panel=a_panel, a_unit=np.asarray(a_unit), bc_bytes=bc_bytes,
        flops=flops, util=util, peak=np.asarray(_peak_of(d, spec)),
    )


def kernel_stats_batch(
    d, t, vmem_budget=None, spec: TPUSpec = DEFAULT_SPEC,
    pre: TilePrecomp | None = None,
) -> KernelStatsBatch:
    """Vectorized `kernel_stats`: ``d`` is a `GemmDesc` or `DescBatch`,
    ``t`` a `TileConfig` or `TileBatch`, ``vmem_budget`` a scalar or array;
    all broadcast together.  This is THE model — the scalar path wraps it.

    Non-GEMM `OpDesc` families (DESIGN.md §14) dispatch to their own
    struct-of-arrays models below; the GEMM path is byte-for-byte the
    pre-heterogeneous one.
    """
    if not isinstance(d, (GemmDesc, DescBatch)):
        return _FAMILY_STATS[family_of(d)](d, t, vmem_budget, spec)
    p = pre if pre is not None else tile_precompute(d, t, spec)
    budget = spec.vmem_bytes if vmem_budget is None else vmem_budget

    # A-panel residency: partial fit ⇒ partial reuse (smooth, not a
    # cliff): the resident fraction of the panel is re-read 1x, the rest
    # tn x.
    resid_frac = np.minimum(np.maximum(
        (budget - p.ws) / p.a_panel, 0.0), 1.0)
    a_resident = resid_frac >= 1.0
    eff_reads = p.tn - resid_frac * (p.tn - 1)
    hbm = eff_reads * p.a_unit + p.bc_bytes

    slots = np.maximum(1, budget // p.ws)
    waves = p.n_tiles / np.minimum(slots, spec.pipeline_fill_tiles * 4)
    occ = np.minimum(1.0, (p.ws + resid_frac * p.a_panel) / budget)
    EVAL_COUNTER.add(np.size(waves))
    return KernelStatsBatch(
        n_tiles=p.n_tiles,
        waves=waves,
        occupancy=occ,
        vmem_bytes=p.ws + np.where(a_resident, p.a_panel, 0.0),
        hbm_bytes=hbm,
        flops=p.flops,
        mxu_util=p.util,
        a_resident=a_resident,
        splits=p.splits,
        streams=p.streams,
    )


def isolated_time_batch(
    d, t, spec: TPUSpec = DEFAULT_SPEC, vmem_budget=None, bw_frac=1.0,
    pre: TilePrecomp | None = None,
) -> np.ndarray:
    """Vectorized `isolated_time` (one launch per evaluation slot; split-K
    and Stream-K kernels pay one extra launch for the reduce/fixup
    epilogue).  Non-GEMM families share the same roofline composition
    over their own stats."""
    if not isinstance(d, (GemmDesc, DescBatch)):
        st = kernel_stats_batch(d, t, vmem_budget, spec)
        compute = st.flops / (spec.peak(_compute_dtype(d)) * st.mxu_util)
        bw = spec.hbm_bw * bw_frac
        memory = st.hbm_bytes / bw
        ramp = spec.pipeline_fill_tiles * (st.hbm_bytes / st.n_tiles / bw)
        return (np.maximum(compute, memory) + ramp
                + spec.launch_overhead_s)
    p = pre if pre is not None else tile_precompute(d, t, spec)
    st = kernel_stats_batch(d, t, vmem_budget, spec, pre=p)
    compute = st.flops / (p.peak * st.mxu_util)
    bw = spec.hbm_bw * bw_frac
    memory = st.hbm_bytes / bw
    # fill/drain bubbles: first/last tiles can't overlap DMA with compute
    ramp = spec.pipeline_fill_tiles * (st.hbm_bytes / st.n_tiles / bw)
    launches = np.where((st.splits > 1) | (st.streams > 0), 2.0, 1.0)
    return (np.maximum(compute, memory) + ramp
            + launches * spec.launch_overhead_s)


def group_time_batch(
    d: GemmDesc, t, cds, spec: TPUSpec = DEFAULT_SPEC,
    pre: TilePrecomp | None = None, tiles_per_cd: bool = False,
) -> np.ndarray:
    """Vectorized *homogeneous* `group_time`: ``cd`` identical members per
    group, one group per (cd, tile) pair.  Returns shape
    ``(len(cds), len(tiles))``.  One batched stats call evaluates every
    (CD share × tile) slot; the member sums use the same left-to-right
    accumulation as the scalar member loop, so results are bitwise equal
    to ``group_time([(d, tile)] * cd)``.

    ``tiles_per_cd=True`` says the tile batch *already carries the CD
    axis as its leading dim* (shape ``(len(cds), ...)``) — used by the
    tuner's Stream-K candidates, whose grid size depends on the CD VMEM
    share — so the share array reshapes onto that axis instead of
    prepending a new one.
    """
    cds = [int(c) for c in np.atleast_1d(cds)]
    p = pre if pre is not None else tile_precompute(d, t, spec)
    # The CD axis is prepended to whatever batch shape (desc × tile) the
    # inputs broadcast to — unless the tiles already carry it in front.
    rest = np.broadcast_shapes(np.shape(p.ws), np.shape(p.n_tiles),
                               np.shape(p.bc_bytes))
    if tiles_per_cd:
        if not rest or rest[0] != len(cds):
            raise ValueError(
                f"tiles_per_cd=True needs a leading CD axis of {len(cds)}, "
                f"got batch shape {rest}")
        shares = np.asarray([spec.vmem_bytes // c for c in cds],
                            np.int64).reshape((len(cds),)
                                              + (1,) * (len(rest) - 1))
    else:
        shares = np.asarray([spec.vmem_bytes // c for c in cds],
                            np.int64).reshape((len(cds),) + (1,) * len(rest))
    st = kernel_stats_batch(d, t, vmem_budget=shares, spec=spec, pre=p)
    comp = np.broadcast_to(st.flops / (p.peak * st.mxu_util),
                           st.hbm_bytes.shape)
    mem = st.hbm_bytes / spec.hbm_bw
    ramp = spec.pipeline_fill_tiles * (st.hbm_bytes / st.n_tiles
                                       / spec.hbm_bw)
    # Stack the four per-member quantities and fold each row's cd copies
    # left-to-right (NOT cd · x, which rounds differently than the scalar
    # member loop).
    quants = np.stack([comp, mem, np.maximum(comp, mem),
                       np.broadcast_to(st.vmem_bytes, mem.shape)])
    acc = quants.copy()
    for r, cd in enumerate(cds):
        row = quants[:, r]
        arow = acc[:, r]
        for _ in range(cd - 1):
            arow += row
    sum_c, sum_m, serial, total_ws = acc
    pressure = total_ws / spec.vmem_bytes
    # pressure > 0 always (tile working sets are positive)
    overlap = np.minimum(1.0, 1.0 / pressure)
    ideal = np.maximum(sum_c, sum_m)
    t_exec = overlap * ideal + (1.0 - overlap) * (
        serial * (1.0 + 0.25 * np.maximum(0.0, pressure - 1.0))
    )
    launches = np.where((st.splits > 1) | (st.streams > 0), 2.0, 1.0)
    return t_exec + ramp + launches * spec.launch_overhead_s


# ------------------------------------------------------------ scalar façade
def kernel_stats(
    d: GemmDesc, t: TileConfig, vmem_budget: int | None = None,
    spec: TPUSpec = DEFAULT_SPEC,
) -> KernelStats:
    return kernel_stats_batch(d, t, vmem_budget, spec).item()


def isolated_time(
    d: GemmDesc, t: TileConfig, spec: TPUSpec = DEFAULT_SPEC,
    vmem_budget: int | None = None, bw_frac: float = 1.0,
) -> float:
    """Modeled latency of one GEMM kernel run alone (one launch)."""
    return float(isolated_time_batch(d, t, spec, vmem_budget, bw_frac))


# Per-piece charge for Kernelet-style op slicing (DESIGN.md §17.1): each
# slice is a real extra launch plus a merge-concat touch of its output.
# Small relative to CP_OVERHEAD_S-scale dispatch — slicing a compute-bound
# prefill into ≤8 pieces costs ~1% of its runtime, so the admission policy
# (runtime.py §17.2) can slice aggressively without cooking the model.
SLICE_OVERHEAD_S = 2e-6


def sliced_time(
    d, t, parts: int, spec: TPUSpec = DEFAULT_SPEC,
) -> float:
    """Modeled latency of running ``d`` as ``parts`` sequential slices.

    Sum of the pieces' isolated times plus `SLICE_OVERHEAD_S` per piece;
    ``parts=1`` charges no overhead and equals `isolated_time`."""
    pieces = d.slice(parts) if getattr(d, "can_slice", False) else [d]
    total = 0.0
    for p in pieces:
        total += float(isolated_time_batch(p, t, spec))
    if len(pieces) > 1:
        total += len(pieces) * SLICE_OVERHEAD_S
    return total


def sequential_time(
    members: Sequence[tuple[GemmDesc, TileConfig]],
    spec: TPUSpec = DEFAULT_SPEC,
) -> float:
    if not members:
        return 0.0
    if not _all_gemm(members):
        acc = 0.0
        for d, t in members:
            acc += float(isolated_time_batch(d, t, spec))
        return acc
    db = DescBatch.from_descs([d for d, _ in members])
    tb = TileBatch.from_tiles([t for _, t in members])
    times = isolated_time_batch(db, tb, spec)
    acc = 0.0
    for v in times:
        acc += float(v)
    return acc


def group_time(
    members: Sequence[tuple[GemmDesc, TileConfig]],
    spec: TPUSpec = DEFAULT_SPEC,
) -> float:
    """Modeled latency of one *grouped* launch executing all members.

    Ideal grouped execution reaches the merged roofline
    ``max(Σ compute_i, Σ memory_i)`` — bubbles of memory-bound members are
    filled by compute-bound members' tiles.  The overlap degrades toward
    serial execution as the aggregate working set overflows VMEM, and
    overflowing also inflates traffic (panel-residency loss accounted per
    member via the VMEM *share*).  Heterogeneous members are evaluated in
    one batched model call; the float folds run left-to-right so the
    result is bitwise identical to the pre-vectorization member loop.

    Mixed-family groups (DESIGN.md §14) take a per-member dispatch loop
    through the same overlap math: the per-family stats supply each
    member's compute/memory/working-set terms, so a decode bundle's QKV
    GEMMs, attention, MoE grouped-GEMM, and scan share one concurrency
    model.  The GEMM-only fast path below is untouched (bitwise).
    """
    G = len(members)
    if G == 0:
        return 0.0
    share = spec.vmem_bytes // G
    if not _all_gemm(members):
        return _group_time_mixed(members, share, spec)
    db = DescBatch.from_descs([d for d, _ in members])
    tb = TileBatch.from_tiles([t for _, t in members])
    st = kernel_stats_batch(db, tb, vmem_budget=share, spec=spec)
    comps = st.flops / (db.peak(spec) * st.mxu_util)
    mems = st.hbm_bytes / spec.hbm_bw
    ramps = spec.pipeline_fill_tiles * (st.hbm_bytes / st.n_tiles
                                        / spec.hbm_bw)
    sum_c = _fold(comps)
    sum_m = _fold(mems)
    serial = _fold(np.maximum(comps, mems))
    total_ws = _fold(st.vmem_bytes)
    return _compose_group_time(
        sum_c, sum_m, serial, total_ws, float(np.max(ramps)),
        bool(np.any((st.splits > 1) | (st.streams > 0))), spec,
    )


def _compose_group_time(
    sum_c: float, sum_m: float, serial: float, total_ws: float,
    max_ramp: float, any_epilogue: bool, spec: TPUSpec,
) -> float:
    """The overlap/pressure composition for one grouped launch (§2): both
    live scalar paths — the GEMM fold (`group_time`) and the mixed-family
    member loop (`_group_time_mixed`) — compose through THIS function, so
    a calibration change cannot silently diverge between them.
    (`group_time_ref` keeps its own copy by design: it is the bitwise
    parity oracle; `group_time_batch` carries the array form.)"""
    pressure = total_ws / spec.vmem_bytes
    overlap = min(1.0, 1.0 / pressure) if pressure > 0 else 1.0
    ideal = max(sum_c, sum_m)
    t_exec = overlap * ideal + (1.0 - overlap) * (
        serial * (1.0 + 0.25 * max(0.0, pressure - 1.0))
    )
    launches = 2.0 if any_epilogue else 1.0
    return t_exec + max_ramp + launches * spec.launch_overhead_s


def _fold(x: np.ndarray) -> float:
    acc = 0.0
    for v in x:
        acc += float(v)
    return acc


def _all_gemm(members) -> bool:
    return all(isinstance(d, GemmDesc) for d, _ in members)


def _compute_dtype(d) -> str:
    """MXU issue dtype of an op — `ScanDesc` stages in f32 regardless of
    the model dtype (§14.1); every other family issues at its dtype."""
    return getattr(d, "compute_dtype", d.dtype)


def _group_time_mixed(members, share: int, spec: TPUSpec) -> float:
    """Heterogeneous-family grouped launch: per-member family stats fed
    through the same overlap/pressure math as the GEMM fold (the ACS-style
    shared resource model — each member sees a 1/G VMEM share)."""
    comps, mems, sers, wss, ramps = [], [], [], [], []
    any_epilogue = False
    for d, t in members:
        st = kernel_stats_batch(d, t, vmem_budget=share, spec=spec).item()
        peak = spec.peak(_compute_dtype(d))
        comps.append(st.flops / (peak * st.mxu_util))
        mems.append(st.hbm_bytes / spec.hbm_bw)
        ramps.append(spec.pipeline_fill_tiles
                     * (st.hbm_bytes / st.n_tiles / spec.hbm_bw))
        sers.append(max(comps[-1], mems[-1]))
        wss.append(st.vmem_bytes)
        any_epilogue = any_epilogue or st.splits > 1 or st.streams > 0
    return _compose_group_time(
        sum(comps), sum(mems), sum(sers), sum(wss), max(ramps),
        any_epilogue, spec,
    )


def speedup_vs_sequential(
    members: Sequence[tuple[GemmDesc, TileConfig]],
    spec: TPUSpec = DEFAULT_SPEC,
) -> float:
    return sequential_time(members, spec) / group_time(members, spec)


# ------------------------------------------------- pure-Python reference
def kernel_stats_ref(
    d: GemmDesc, t: TileConfig, vmem_budget: int | None = None,
    spec: TPUSpec = DEFAULT_SPEC,
) -> KernelStats:
    """Pure-Python port of the model — the parity oracle for the batched
    path and the scalar-loop baseline timed by `benchmarks/tuning.py`.
    Keep every operation in the same order as the batched path
    (`tile_precompute` + `kernel_stats_batch`) so results stay bitwise
    equal."""
    EVAL_COUNTER.add(1)
    budget = vmem_budget if vmem_budget is not None else spec.vmem_bytes
    bm = min(t.bm, _round_up(d.M, spec.mxu_dim))
    bn = min(t.bn, _round_up(d.N, spec.mxu_dim))
    bk = min(t.bk, _round_up(d.K, spec.mxu_dim))
    tm, tn, tk = _cdiv(d.M, bm), _cdiv(d.N, bn), _cdiv(d.K, bk)
    s = min(t.split_k, tk)
    n_tiles = tm * tn * s * d.batch
    sk = t.stream_k
    total = tm * tn * tk * d.batch
    ipw = _cdiv(total, max(min(sk, total), 1))
    g_live = _cdiv(total, ipw)
    if sk > 0:
        n_tiles = g_live
        streams = g_live
    else:
        streams = 0

    ws = (2 * (bm * bk + bk * bn) * d.in_bytes
          + bm * bn * 4 + bm * bn * d.in_bytes)
    a_panel = bm * d.K * d.in_bytes / s
    a_stream = _STRIDED_DMA if d.ta else 1.0
    b_stream = _STRIDED_DMA if d.tb else 1.0
    a_unit = d.M * d.K * d.in_bytes * d.batch * a_stream
    b_bytes = tm * (d.K * d.N * d.in_bytes * d.batch) * b_stream
    c_bytes = d.M * d.N * d.in_bytes * d.batch
    part_bytes = s * (2 * (d.M * d.N * 4) * d.batch) if s > 1 else 0.0
    if sk > 0:
        period = tk // math.gcd(ipw, tk)
        straddle = (g_live - 1) - (g_live - 1) // period
        part_bytes = straddle * (2.0 * (bm * bn * 4))
    bc_bytes = (b_bytes + c_bytes) + part_bytes

    resid_frac = min(max((budget - ws) / a_panel, 0.0), 1.0)
    a_resident = resid_frac >= 1.0
    eff_reads = tn - resid_frac * (tn - 1)
    hbm = eff_reads * a_unit + bc_bytes

    flops = 2.0 * (tm * bm) * (tn * bn) * (tk * bk) * d.batch
    util = (
        _align_eff(bm, spec.mxu_dim)
        * _align_eff(bn, spec.mxu_dim)
        * _align_eff(bk, spec.mxu_dim)
    )
    slots = max(1, budget // ws)
    waves = n_tiles / min(slots, spec.pipeline_fill_tiles * 4)
    occ = min(1.0, (ws + resid_frac * a_panel) / budget)
    return KernelStats(
        n_tiles=n_tiles,
        waves=waves,
        occupancy=occ,
        vmem_bytes=ws + (a_panel if a_resident else 0.0),
        hbm_bytes=hbm,
        flops=flops,
        mxu_util=util,
        a_resident=a_resident,
        splits=s,
        streams=streams,
    )


def isolated_time_ref(
    d: GemmDesc, t: TileConfig, spec: TPUSpec = DEFAULT_SPEC,
    vmem_budget: int | None = None, bw_frac: float = 1.0,
) -> float:
    st = kernel_stats_ref(d, t, vmem_budget, spec)
    compute = st.flops / (spec.peak(d.dtype) * st.mxu_util)
    bw = spec.hbm_bw * bw_frac
    memory = st.hbm_bytes / bw
    ramp = spec.pipeline_fill_tiles * (st.hbm_bytes / st.n_tiles / bw)
    launches = 2.0 if (st.splits > 1 or st.streams > 0) else 1.0
    return max(compute, memory) + ramp + launches * spec.launch_overhead_s


def group_time_ref(
    members: Sequence[tuple[GemmDesc, TileConfig]],
    spec: TPUSpec = DEFAULT_SPEC,
) -> float:
    G = len(members)
    if G == 0:
        return 0.0
    share = spec.vmem_bytes // G
    comps, mems, ramps, sers, wss = [], [], [], [], []
    any_split = False
    for d, t in members:
        st = kernel_stats_ref(d, t, vmem_budget=share, spec=spec)
        comps.append(st.flops / (spec.peak(d.dtype) * st.mxu_util))
        mems.append(st.hbm_bytes / spec.hbm_bw)
        ramps.append(spec.pipeline_fill_tiles
                     * (st.hbm_bytes / st.n_tiles / spec.hbm_bw))
        sers.append(max(comps[-1], mems[-1]))
        wss.append(st.vmem_bytes)
        any_split = any_split or st.splits > 1 or st.streams > 0
    pressure = sum(wss) / spec.vmem_bytes
    overlap = min(1.0, 1.0 / pressure) if pressure > 0 else 1.0
    ideal = max(sum(comps), sum(mems))
    serial = sum(sers)
    t_exec = overlap * ideal + (1.0 - overlap) * (
        serial * (1.0 + 0.25 * max(0.0, pressure - 1.0))
    )
    launches = 2.0 if any_split else 1.0
    return t_exec + max(ramps) + launches * spec.launch_overhead_s


# ----------------------------------------- per-family op models (§14)
# Each family mirrors the GEMM model's structure: a geometry helper
# (budget-independent tile math), a vectorized stats function over
# (TileBatch × budget) arrays, and a pure-Python `*_ref` parity oracle.
# All times compose through the same `isolated_time_batch` /
# `group_time` rooflines, so a mixed-family group is evaluated with one
# consistent overlap model.

def _tile_dims(t):
    return np.asarray(t.bm), np.asarray(t.bn), np.asarray(t.bk)


def _attn_geom(d: AttentionDesc, t, spec: TPUSpec):
    """(bq, bkv, tq, tkv, ws, kv_panel) for the flash kernel: kv is the
    sequential inner sweep (the GEMM K analogue), q blocks × (B·Hq) are
    the parallel grid."""
    bm, bn, _ = _tile_dims(t)
    bq = np.minimum(bm, _round_up(d.Sq, 8))
    bkv = np.minimum(bn, _round_up(d.Skv, spec.mxu_dim))
    tq = _cdiv(d.Sq, bq)
    tkv = _cdiv(d.Skv, bkv)
    ib = d.in_bytes
    # double-buffered K/V tiles + Q tile + online-softmax scratch
    # (m, l replicated to 128 lanes; f32 acc) + output tile.
    ws = (2 * (2 * bkv * d.D * ib) + bq * d.D * ib
          + (2 * bq * 128 + bq * d.D) * 4 + bq * d.D * ib)
    kv_panel = 2.0 * d.Skv * d.D * ib      # one head's K+V, residency unit
    return bq, bkv, tq, tkv, ws, kv_panel


def attention_stats_batch(
    d: AttentionDesc, t, vmem_budget=None, spec: TPUSpec = DEFAULT_SPEC,
) -> KernelStatsBatch:
    """O(Sq·Skv) attention with causal credit: the block-sparse causal
    iteration skips masked kv blocks (kernel `pl.when` frontier), so
    FLOPs and K/V traffic scale by `causal_credit`.  K/V residency in
    the VMEM share plays the GEMM A-panel role — losing it at high CD
    re-reads K/V once per q block."""
    budget = spec.vmem_bytes if vmem_budget is None else vmem_budget
    bq, bkv, tq, tkv, ws, kv_panel = _attn_geom(d, t, spec)
    credit = d.causal_credit
    n_tiles = d.B * d.Hq * tq
    resid_frac = np.minimum(np.maximum(
        (budget - ws) / kv_panel, 0.0), 1.0)
    kv_resident = resid_frac >= 1.0
    eff_reads = tq - resid_frac * (tq - 1)
    kv_unit = d.B * d.Hkv * d.Skv * d.D * d.in_bytes * 2.0 * credit
    qo_bytes = 2.0 * d.B * d.Hq * d.Sq * d.D * d.in_bytes
    hbm = eff_reads * kv_unit + qo_bytes
    flops = 4.0 * d.B * d.Hq * (tq * bq) * (tkv * bkv) * d.D * credit
    util = (_align_eff(bq, spec.mxu_dim) * _align_eff(bkv, spec.mxu_dim)
            * _align_eff(d.D, spec.mxu_dim))
    slots = np.maximum(1, budget // ws)
    waves = n_tiles / np.minimum(slots, spec.pipeline_fill_tiles * 4)
    occ = np.minimum(1.0, (ws + resid_frac * kv_panel) / budget)
    EVAL_COUNTER.add(np.size(waves))
    return KernelStatsBatch(
        n_tiles=np.asarray(n_tiles), waves=np.asarray(waves),
        occupancy=np.asarray(occ),
        vmem_bytes=np.asarray(ws + np.where(kv_resident, kv_panel, 0.0)),
        hbm_bytes=np.asarray(hbm), flops=np.asarray(flops),
        mxu_util=np.asarray(util), a_resident=np.asarray(kv_resident),
        splits=np.ones_like(np.asarray(n_tiles)),
        streams=np.zeros_like(np.asarray(n_tiles)),
    )


def _grouped_geom(d: GroupedGemmDesc, t, spec: TPUSpec):
    """Ragged expert pool: per-expert row counts prepend an expert axis
    that is reduced inside the stats, so the public shape matches the
    tile/budget broadcast like every other family."""
    bm, bn, bk = _tile_dims(t)
    mxu = spec.mxu_dim
    bm_c = np.minimum(bm, _round_up(d.M, mxu))
    bn_c = np.minimum(bn, _round_up(d.N, mxu))
    bk_c = np.minimum(bk, _round_up(d.K, mxu))
    ib = d.in_bytes
    ws = (2 * (bm_c * bk_c + bk_c * bn_c) * ib
          + bm_c * bn_c * 4 + bm_c * bn_c * ib)
    a_panel = bm_c * d.K * ib
    return bm_c, bn_c, bk_c, ws, a_panel


def grouped_stats_batch(
    d: GroupedGemmDesc, t, vmem_budget=None, spec: TPUSpec = DEFAULT_SPEC,
) -> KernelStatsBatch:
    """Ragged grouped GEMM: G experts, per-expert rows padded up to the
    bm block (the ragged launch's tail-quantization waste), expert
    weights streamed once per m-tile sweep."""
    budget = spec.vmem_bytes if vmem_budget is None else vmem_budget
    bm_c, bn_c, bk_c, ws, a_panel = _grouped_geom(d, t, spec)
    rows = np.asarray(d.row_vector(), np.int64)
    base = np.broadcast_shapes(np.shape(bm_c), np.shape(ws),
                               np.shape(np.asarray(budget)))
    r = rows.reshape((d.G,) + (1,) * len(base))
    bm_e = np.minimum(bm_c, _round_up(np.maximum(r, 1), 8))
    tm = np.where(r > 0, _cdiv(np.maximum(r, 1), bm_e), 0)
    tn = _cdiv(d.N, bn_c)
    tk = _cdiv(d.K, bk_c)
    ib = d.in_bytes
    n_tiles = np.maximum((tm * tn).sum(0), 1)
    resid_frac = np.minimum(np.maximum(
        (budget - ws) / a_panel, 0.0), 1.0)
    a_resident = resid_frac >= 1.0
    eff_reads = tn - resid_frac * (tn - 1)
    a_unit = d.M * d.K * ib
    b_bytes = tm.sum(0) * (d.K * d.N * ib)
    c_bytes = d.M * d.N * ib
    hbm = eff_reads * a_unit + b_bytes + c_bytes
    flops = 2.0 * (tm * bm_e).sum(0) * (tn * bn_c) * (tk * bk_c)
    util = (_align_eff(bm_c, spec.mxu_dim) * _align_eff(bn_c, spec.mxu_dim)
            * _align_eff(bk_c, spec.mxu_dim))
    slots = np.maximum(1, budget // ws)
    waves = n_tiles / np.minimum(slots, spec.pipeline_fill_tiles * 4)
    occ = np.minimum(1.0, (ws + resid_frac * a_panel) / budget)
    EVAL_COUNTER.add(np.size(waves))
    return KernelStatsBatch(
        n_tiles=np.asarray(n_tiles), waves=np.asarray(waves),
        occupancy=np.asarray(occ),
        vmem_bytes=np.asarray(ws + np.where(a_resident, a_panel, 0.0)),
        hbm_bytes=np.asarray(hbm), flops=np.asarray(flops),
        mxu_util=np.asarray(util), a_resident=np.asarray(a_resident),
        splits=np.ones_like(np.asarray(n_tiles)),
        streams=np.zeros_like(np.asarray(n_tiles)),
    )


def _scan_geom(d: ScanDesc, t, spec: TPUSpec):
    """(L, n_chunks, ws): chunk length L is the tunable axis (tile.bm);
    the chunk sweep is sequential per (batch, head)."""
    bm, _, _ = _tile_dims(t)
    L = np.maximum(np.minimum(bm, _round_up(d.T, 8)), 8)
    n_chunks = _cdiv(d.T, L)
    ib = d.in_bytes                       # f32 staging (4 B)
    # double-buffered chunk inputs (xd, da, B, C) + state scratch + y out
    ws = 2 * (L * d.P + L + 2 * L * d.N) * ib + d.N * d.P * 4 + L * d.P * ib
    return L, n_chunks, ws


def scan_stats_batch(
    d: ScanDesc, t, vmem_budget=None, spec: TPUSpec = DEFAULT_SPEC,
) -> KernelStatsBatch:
    """Chunked SSD scan: bandwidth-bound streaming of (xd, da, B, C, y)
    with a *sequential* chunk sweep per (b, h) — parallelism is capped at
    B·H, so waves floor at n_chunks regardless of VMEM share (the
    family's defining concurrency behaviour: it fills bubbles of
    compute-bound co-runners without competing for MXU)."""
    budget = spec.vmem_bytes if vmem_budget is None else vmem_budget
    L, n_chunks, ws = _scan_geom(d, t, spec)
    BH = d.B * d.H
    ib = d.in_bytes
    n_tiles = BH * n_chunks
    hbm = (BH * ((2 * d.T * d.P + d.T + 2 * d.T * d.N) * ib
                 + 2 * d.N * d.P * 4)) * np.ones_like(np.asarray(ws, float))
    flops = BH * n_chunks * (2.0 * L * L * (d.N + d.P) + 4.0 * L * d.N * d.P)
    util = (_align_eff(L, spec.mxu_dim) * _align_eff(d.N, spec.mxu_dim)
            * _align_eff(d.P, spec.mxu_dim))
    slots = np.maximum(1, budget // ws)
    # sequential chunk dim: at least n_chunks waves even with free slots
    waves = n_chunks * np.maximum(
        1.0, BH / np.minimum(slots, spec.pipeline_fill_tiles * 4))
    occ = np.minimum(1.0, ws / budget)
    EVAL_COUNTER.add(np.size(waves))
    return KernelStatsBatch(
        n_tiles=np.asarray(n_tiles), waves=np.asarray(waves),
        occupancy=np.asarray(occ), vmem_bytes=np.asarray(ws, float),
        hbm_bytes=np.asarray(hbm), flops=np.asarray(flops),
        mxu_util=np.asarray(util),
        a_resident=np.zeros(np.shape(np.asarray(ws)), bool),
        splits=np.ones_like(np.asarray(n_tiles)),
        streams=np.zeros_like(np.asarray(n_tiles)),
    )


_FAMILY_STATS = {
    "flash_attention": attention_stats_batch,
    "grouped_gemm": grouped_stats_batch,
    "mamba_scan": scan_stats_batch,
}


def op_tile_ws(d, t, spec: TPUSpec = DEFAULT_SPEC):
    """Raw per-instance working set of a (desc, tile) pair for any family
    — the tuner's feasibility predicate (`ws ≤ RC budget`)."""
    fam = family_of(d)
    if fam == "flash_attention":
        return _attn_geom(d, t, spec)[4]
    if fam == "grouped_gemm":
        return _grouped_geom(d, t, spec)[3]
    if fam == "mamba_scan":
        return _scan_geom(d, t, spec)[2]
    return t.vmem_bytes(d.in_bytes)


def op_kernel_stats_ref(
    d, t: TileConfig, vmem_budget: int | None = None,
    spec: TPUSpec = DEFAULT_SPEC,
) -> KernelStats:
    """Pure-Python parity oracle for the per-family batched models
    (mirrors `kernel_stats_ref`'s role for the GEMM path; same operation
    order as the batched code so results stay bitwise equal)."""
    fam = family_of(d)
    if fam == "gemm":
        return kernel_stats_ref(d, t, vmem_budget, spec)
    EVAL_COUNTER.add(1)
    budget = vmem_budget if vmem_budget is not None else spec.vmem_bytes
    mxu = spec.mxu_dim
    if fam == "flash_attention":
        bq = min(t.bm, _round_up(d.Sq, 8))
        bkv = min(t.bn, _round_up(d.Skv, mxu))
        tq, tkv = _cdiv(d.Sq, bq), _cdiv(d.Skv, bkv)
        ib = d.in_bytes
        ws = (2 * (2 * bkv * d.D * ib) + bq * d.D * ib
              + (2 * bq * 128 + bq * d.D) * 4 + bq * d.D * ib)
        kv_panel = 2.0 * d.Skv * d.D * ib
        credit = d.causal_credit
        n_tiles = d.B * d.Hq * tq
        resid_frac = min(max((budget - ws) / kv_panel, 0.0), 1.0)
        kv_resident = resid_frac >= 1.0
        eff_reads = tq - resid_frac * (tq - 1)
        kv_unit = d.B * d.Hkv * d.Skv * d.D * ib * 2.0 * credit
        qo_bytes = 2.0 * d.B * d.Hq * d.Sq * d.D * ib
        hbm = eff_reads * kv_unit + qo_bytes
        flops = 4.0 * d.B * d.Hq * (tq * bq) * (tkv * bkv) * d.D * credit
        util = (_align_eff(bq, mxu) * _align_eff(bkv, mxu)
                * _align_eff(d.D, mxu))
        slots = max(1, budget // ws)
        waves = n_tiles / min(slots, spec.pipeline_fill_tiles * 4)
        occ = min(1.0, (ws + resid_frac * kv_panel) / budget)
        return KernelStats(
            n_tiles=int(n_tiles), waves=float(waves), occupancy=float(occ),
            vmem_bytes=float(ws + (kv_panel if kv_resident else 0.0)),
            hbm_bytes=float(hbm), flops=float(flops), mxu_util=float(util),
            a_resident=bool(kv_resident), splits=1,
        )
    if fam == "grouped_gemm":
        bm_c = min(t.bm, _round_up(d.M, mxu))
        bn_c = min(t.bn, _round_up(d.N, mxu))
        bk_c = min(t.bk, _round_up(d.K, mxu))
        ib = d.in_bytes
        ws = (2 * (bm_c * bk_c + bk_c * bn_c) * ib
              + bm_c * bn_c * 4 + bm_c * bn_c * ib)
        a_panel = bm_c * d.K * ib
        rows = d.row_vector()
        tn, tk = _cdiv(d.N, bn_c), _cdiv(d.K, bk_c)
        tm_sum, padded_m = 0, 0
        for r in rows:
            if r <= 0:
                continue
            bm_e = min(bm_c, _round_up(max(r, 1), 8))
            tm = _cdiv(max(r, 1), bm_e)
            tm_sum += tm
            padded_m += tm * bm_e
        n_tiles = max(tm_sum * tn, 1)
        resid_frac = min(max((budget - ws) / a_panel, 0.0), 1.0)
        a_resident = resid_frac >= 1.0
        eff_reads = tn - resid_frac * (tn - 1)
        hbm = (eff_reads * (d.M * d.K * ib) + tm_sum * (d.K * d.N * ib)
               + d.M * d.N * ib)
        flops = 2.0 * padded_m * (tn * bn_c) * (tk * bk_c)
        util = (_align_eff(bm_c, mxu) * _align_eff(bn_c, mxu)
                * _align_eff(bk_c, mxu))
        slots = max(1, budget // ws)
        waves = n_tiles / min(slots, spec.pipeline_fill_tiles * 4)
        occ = min(1.0, (ws + resid_frac * a_panel) / budget)
        return KernelStats(
            n_tiles=int(n_tiles), waves=float(waves), occupancy=float(occ),
            vmem_bytes=float(ws + (a_panel if a_resident else 0.0)),
            hbm_bytes=float(hbm), flops=float(flops), mxu_util=float(util),
            a_resident=bool(a_resident), splits=1,
        )
    # mamba_scan
    L = max(min(t.bm, _round_up(d.T, 8)), 8)
    n_chunks = _cdiv(d.T, L)
    BH = d.B * d.H
    ib = d.in_bytes
    ws = 2 * (L * d.P + L + 2 * L * d.N) * ib + d.N * d.P * 4 + L * d.P * ib
    n_tiles = BH * n_chunks
    hbm = BH * ((2 * d.T * d.P + d.T + 2 * d.T * d.N) * ib
                + 2 * d.N * d.P * 4)
    flops = BH * n_chunks * (2.0 * L * L * (d.N + d.P) + 4.0 * L * d.N * d.P)
    util = (_align_eff(L, mxu) * _align_eff(d.N, mxu)
            * _align_eff(d.P, mxu))
    slots = max(1, budget // ws)
    waves = n_chunks * max(1.0, BH / min(slots, spec.pipeline_fill_tiles * 4))
    occ = min(1.0, ws / budget)
    return KernelStats(
        n_tiles=int(n_tiles), waves=float(waves), occupancy=float(occ),
        vmem_bytes=float(ws), hbm_bytes=float(hbm), flops=float(flops),
        mxu_util=float(util), a_resident=False, splits=1,
    )


# ------------------------------------------------------------------ helpers
def _cdiv(a, b):
    return -(-a // b)


def _round_up(a, b):
    return _cdiv(a, b) * b


def _align_eff(dim, mxu):
    return dim / (_cdiv(dim, mxu) * mxu)


# --------------------------------------------------- self-calibration (§16)
@dataclass
class ClassCalibration:
    """Per-(family, compat-class) correction state.

    ``log_factor`` is the EWMA of log(achieved/modeled) — the
    multiplicative correction is ``exp(log_factor)``; ``drift`` is the
    EWMA of |log(achieved/modeled)| against the *raw* model, the stale-
    entry detector (a well-modeled class sits near 0, a biased one near
    |log bias| regardless of sign)."""

    log_factor: float = 0.0
    drift: float = 0.0
    n: int = 0


class CostCalibrator:
    """Online multiplicative correction of the roofline model (DESIGN.md
    §16): fit per-(family, compat-class) factors from the modeled-vs-
    achieved ratios the runtime telemetry collects, so CD selection can
    rank groups by ``factor · modeled_time`` instead of trusting the
    first-principles constants.

    Updates are EWMAs in log space (the first sample initializes the
    state directly, so a constant-bias stream converges immediately and
    stays put).  Working in ratios makes every statistic scale-invariant:
    multiplying modeled AND achieved times by any constant leaves the
    factors unchanged, and applying one class's factor to all of that
    class's candidates can never flip a modeled ordering (property-tested
    in `tests/test_calibration.py`).

    ``pop_stale()`` is the drift detector: classes whose ``drift`` EWMA
    exceeds ``drift_threshold`` (in |log ratio| units — 0.35 ≈ a 1.4×
    modeled-vs-achieved gap) are returned once and their drift state
    reset, so the caller can queue ONE background re-tune per excursion
    (`Runtime.process_retunes`) instead of re-tuning every flush."""

    def __init__(self, alpha: float = 0.2, drift_threshold: float = 0.35):
        self.alpha = float(alpha)
        self.drift_threshold = float(drift_threshold)
        self._state: dict[tuple[str, str], ClassCalibration] = {}

    # ------------------------------------------------------------- update
    def update(
        self, family: str, class_key: str, modeled_s: float, achieved_s: float
    ) -> None:
        """Fold one modeled-vs-achieved observation into the class state.
        Non-positive and non-finite times carry no ratio information and
        are ignored — a NaN/Inf achieved time (hung or faulted launch,
        DESIGN.md §18) must never poison the EWMA state."""
        if (modeled_s <= 0 or achieved_s <= 0
                or not (math.isfinite(modeled_s)
                        and math.isfinite(achieved_s))):
            return
        r = math.log(achieved_s / modeled_s)
        st = self._state.get((family, class_key))
        if st is None or st.n == 0:
            self._state[(family, class_key)] = ClassCalibration(
                log_factor=r, drift=abs(r), n=1)
            return
        a = self.alpha
        st.log_factor = (1.0 - a) * st.log_factor + a * r
        st.drift = (1.0 - a) * st.drift + a * abs(r)
        st.n += 1

    # -------------------------------------------------------------- query
    def factor(self, family: str, class_key: str) -> float:
        """Multiplicative correction for a class; 1.0 until observed."""
        st = self._state.get((family, class_key))
        return 1.0 if st is None or st.n == 0 else math.exp(st.log_factor)

    def correct(
        self, family: str, class_key: str, modeled_s: float
    ) -> float:
        """``factor · modeled`` — returns ``modeled_s`` untouched (same
        float object, bitwise) for classes with no observations."""
        st = self._state.get((family, class_key))
        if st is None or st.n == 0:
            return modeled_s
        return modeled_s * math.exp(st.log_factor)

    def __len__(self) -> int:
        return len(self._state)

    def stale_classes(self) -> list[tuple[str, str]]:
        """Classes whose drift EWMA currently exceeds the threshold."""
        return [k for k, st in sorted(self._state.items())
                if st.drift > self.drift_threshold]

    def pop_stale(self) -> list[tuple[str, str]]:
        """`stale_classes`, resetting each returned class's drift state so
        one bias excursion queues one re-tune (the factor survives — the
        correction stays live while the re-tune is pending)."""
        stale = self.stale_classes()
        for k in stale:
            self._state[k].drift = 0.0
        return stale

    # ------------------------------------------------------------ persist
    def to_json(self) -> dict:
        return {
            "alpha": self.alpha,
            "drift_threshold": self.drift_threshold,
            "classes": {
                f"{fam}|{ck}": {"log_factor": st.log_factor,
                                "drift": st.drift, "n": st.n}
                for (fam, ck), st in sorted(self._state.items())
            },
        }

    @classmethod
    def from_json(cls, blob: dict) -> "CostCalibrator":
        cal = cls(alpha=blob.get("alpha", 0.2),
                  drift_threshold=blob.get("drift_threshold", 0.35))
        for key, st in blob.get("classes", {}).items():
            fam, ck = key.split("|", 1)
            cal._state[(fam, ck)] = ClassCalibration(
                log_factor=float(st["log_factor"]),
                drift=float(st["drift"]), n=int(st["n"]))
        return cal
