"""Measured-time harness for the GO pipeline — DESIGN.md §16.

The paper picks GO-kernels from *profiled* concurrent execution; the
repo's tuner ranks candidates with the analytical roofline model
(CPU-only containers).  This module closes that gap: it times the real
pallas launches through the **same** launch shapes and `OpDesc` family
adapters the scheduler dispatches (`core.scheduler.execute_schedule`),
so a measured number is attached to exactly the kernel the plan would
run.

Backends: interpret-mode CPU is a first-class backend (every container
has it; its timings calibrate candidate *ordering*, not absolute TPU
latency — see README "Measured vs modeled"), and the identical code
path times real hardware when a TPU is attached (``interpret=False``).

Discipline per measurement:

- operands are synthesized once per request (`synth_request`) and the
  launch is jitted/warmed for ``warmup`` iterations whose timings are
  *discarded* (compilation + cache effects);
- each of ``repeats`` timed iterations brackets the launch with an
  injectable ``clock`` and `block_until_ready` on every output, so
  async dispatch cannot leak out of the bracket;
- one wild sample cannot skew the result: samples beyond
  ``outlier_k`` median-absolute-deviations are rejected, then the
  median of the survivors is reported (median-of-k).

`Measurement.run_id` is a *timestamp-free* deterministic id (hash of
the work + harness settings), so measured GO-library entries (schema
v5, `core/library.py`) stay byte-stable across reruns.
"""
from __future__ import annotations

import argparse
import hashlib
import math
import statistics
import time
from dataclasses import dataclass, replace as dc_replace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.cost_model import RC_FRACTIONS, TPUSpec, device_spec
from repro.core.gemm_desc import GemmDesc
from repro.core.op_desc import family_of
from repro.core.scheduler import (
    GemmRequest,
    GroupPlan,
    Schedule,
    execute_schedule,
)
from repro.kernels.dispatch import interpret_mode
from repro.kernels.gemm.ops import TileConfig


def backend_tag(interpret: bool | None = True) -> str:
    """Stable backend id persisted with measured entries: ``"tpu"`` only
    when actually timing hardware, else ``"interpret-<platform>"`` (the
    calibrate-ordering-only backends)."""
    platform = jax.devices()[0].platform
    if not interpret and platform == "tpu":
        return "tpu"
    return f"interpret-{platform}"


@dataclass(frozen=True)
class Measurement:
    """One measured launch: median-of-k seconds + provenance."""

    time_s: float
    samples: tuple          # kept post-warmup samples, seconds
    n: int                  # number of kept samples (after rejection)
    backend: str
    run_id: str
    hangs: int = 0          # timed samples that blew the watchdog deadline

    @property
    def finite(self) -> bool:
        return math.isfinite(self.time_s) and self.time_s > 0.0


def reject_outliers(samples: Sequence[float], k: float = 4.0) -> List[float]:
    """Drop samples farther than ``k`` robust deviations from the median.

    The deviation scale is ``max(MAD, 5% of median)`` — the relative
    floor keeps an all-identical sample set (MAD = 0) from rejecting
    nothing-is-an-outlier into everything-is-an-outlier."""
    vals = list(samples)
    if len(vals) <= 2:
        return vals
    med = statistics.median(vals)
    mad = statistics.median(abs(v - med) for v in vals)
    scale = max(mad, 0.05 * abs(med))
    if scale <= 0.0:
        return vals
    kept = [v for v in vals if abs(v - med) <= k * scale]
    return kept or [med]


def synth_request(desc, seed: int = 0) -> GemmRequest:
    """Random operands for any `OpDesc`, shaped exactly as the family op
    consumes them (`scheduler._run_op` positional order) — the adapter
    contract `tests/test_measure.py` round-trips."""
    fam = family_of(desc)
    key = jax.random.PRNGKey(seed)
    if fam == "gemm":
        if desc.batch != 1:
            raise ValueError(
                "B-GEMMs have no grouped execute path yet (shadow-only); "
                f"cannot measure {desc.key()}")
        dt = desc.jnp_dtype()
        a_shape = (desc.K, desc.M) if desc.ta else (desc.M, desc.K)
        b_shape = (desc.N, desc.K) if desc.tb else (desc.K, desc.N)
        a = jax.random.normal(jax.random.fold_in(key, 0), a_shape, dt)
        b = jax.random.normal(jax.random.fold_in(key, 1), b_shape, dt)
        return GemmRequest(desc=desc, a=a, b=b)
    if fam == "flash_attention":
        dt = jnp.bfloat16 if desc.dtype == "bf16" else jnp.float32
        q = jax.random.normal(jax.random.fold_in(key, 0),
                              (desc.B, desc.Hq, desc.Sq, desc.D), dt)
        k = jax.random.normal(jax.random.fold_in(key, 1),
                              (desc.B, desc.Hkv, desc.Skv, desc.D), dt)
        v = jax.random.normal(jax.random.fold_in(key, 2),
                              (desc.B, desc.Hkv, desc.Skv, desc.D), dt)
        return GemmRequest(desc=desc, inputs=(q, k, v))
    if fam == "grouped_gemm":
        dt = jnp.bfloat16 if desc.dtype == "bf16" else jnp.float32
        a = jax.random.normal(jax.random.fold_in(key, 0),
                              (desc.M, desc.K), dt)
        b = jax.random.normal(jax.random.fold_in(key, 1),
                              (desc.G, desc.K, desc.N), dt)
        return GemmRequest(desc=desc, inputs=(a, b))
    if fam == "mamba_scan":
        # The scan kernel stages everything in f32 (op_desc.ScanDesc).
        xd = jax.random.normal(jax.random.fold_in(key, 0),
                               (desc.B, desc.T, desc.H, desc.P), jnp.float32)
        da = -jnp.abs(jax.random.normal(
            jax.random.fold_in(key, 1), (desc.B, desc.T, desc.H),
            jnp.float32))
        Bm = jax.random.normal(jax.random.fold_in(key, 2),
                               (desc.B, desc.T, desc.H, desc.N), jnp.float32)
        Cm = jax.random.normal(jax.random.fold_in(key, 3),
                               (desc.B, desc.T, desc.H, desc.N), jnp.float32)
        return GemmRequest(desc=desc, inputs=(xd, da, Bm, Cm))
    raise ValueError(f"unknown op family: {fam}")


def schedule_for(desc, tile: TileConfig, cd: int = 1) -> Schedule:
    """The one-group `Schedule` the scheduler would emit for ``cd``
    identical copies of ``desc`` at ``tile`` — grouped launch for plain
    GEMMs, per-member mixed launch for the other families, single below
    CD 2.  Modeled time is left 0: this schedule exists to be *timed*."""
    if cd <= 1:
        mode = "single"
    elif family_of(desc) == "gemm":
        mode = "grouped"
    else:
        mode = "mixed"
    gp = GroupPlan(
        indices=list(range(max(cd, 1))), cd=max(cd, 1), tile=tile,
        mode=mode, modeled_time_s=0.0,
        tiles=[tile] * cd if mode == "mixed" else None)
    return Schedule(groups=[gp])


def _run_key(desc_keys, tiles, cd, backend, warmup, repeats, seed) -> str:
    blob = "|".join([
        ",".join(desc_keys),
        ",".join(t.key() for t in tiles),
        str(cd), backend, str(warmup), str(repeats), str(seed),
    ])
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


class Measurer:
    """The timing harness.  ``clock`` is injectable (tests script it to
    verify warmup exclusion and outlier rejection without real sleeps);
    ``interpret=None`` follows the backend (`interpret_mode`): interpret
    on the CPU, compiled kernels timed on a TPU."""

    def __init__(
        self,
        spec: TPUSpec | None = None,
        *,
        warmup: int = 1,
        repeats: int = 5,
        interpret: bool | None = None,
        clock=time.perf_counter,
        outlier_k: float = 4.0,
        seed: int = 0,
        deadline_s: float | None = None,
    ):
        self.spec = device_spec() if spec is None else spec
        self.warmup = max(0, int(warmup))
        self.repeats = max(1, int(repeats))
        self.interpret = interpret_mode() if interpret is None else interpret
        self.clock = clock
        self.outlier_k = float(outlier_k)
        self.seed = int(seed)
        self.backend = backend_tag(self.interpret)
        # Watchdog (DESIGN.md §18.4): a timed sample whose clock bracket
        # exceeds the deadline is recorded as ``inf`` — MAD rejection
        # discards a minority of hangs, and an all-hung launch yields a
        # non-finite median that `Measurement.finite` (and the CLI)
        # flags instead of wedging or silently averaging garbage.
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.hangs = 0          # cumulative across this Measurer's calls

    # ------------------------------------------------------------ timing
    def measure_schedule(
        self, requests: Sequence[GemmRequest], sched: Schedule,
    ) -> Measurement:
        """Time one schedule: ``warmup`` discarded iterations, then
        ``repeats`` clock-bracketed iterations with `block_until_ready`
        on every output; outlier-rejected median of the kept samples."""
        for r in requests:
            has = ((r.a is not None and r.b is not None)
                   if family_of(r.desc) == "gemm" else r.inputs is not None)
            if not has:
                raise ValueError(
                    "shadow request (no operands) cannot be measured — "
                    "synthesize operands via synth_request()")
        samples: List[float] = []
        for _ in range(self.warmup + self.repeats):
            t0 = self.clock()
            outs = execute_schedule(requests, sched,
                                    interpret=self.interpret)
            ran = [o for o in outs if o is not None]
            if not ran:
                raise ValueError(
                    "nothing executed — requests carry no operands "
                    "(shadow dispatch cannot be measured)")
            for o in ran:
                o.block_until_ready()
            dt = self.clock() - t0
            if self.deadline_s is not None and dt > self.deadline_s:
                dt = math.inf   # watchdog: hung sample, see __init__
            samples.append(dt)
        timed = samples[self.warmup:]
        hangs = sum(1 for v in timed if math.isinf(v))
        self.hangs += hangs
        kept = reject_outliers(timed, self.outlier_k)
        gp = sched.groups[0]
        run_id = _run_key(
            [r.desc.key() for r in requests],
            [gp.tile], gp.cd, self.backend,
            self.warmup, self.repeats, self.seed)
        return Measurement(
            time_s=float(statistics.median(kept)), samples=tuple(kept),
            n=len(kept), backend=self.backend, run_id=run_id,
            hangs=hangs)

    def measure_group(self, desc, tile: TileConfig, cd: int = 1) -> Measurement:
        """Measure ``cd`` concurrent copies of ``desc`` at ``tile`` via
        the scheduler's launch shape for that pool."""
        reqs = [synth_request(desc, seed=self.seed + i) for i in range(max(cd, 1))]
        return self.measure_schedule(reqs, schedule_for(desc, tile, cd))

    def measure_entry(
        self, desc, entry, cds: Sequence[int] | None = None,
    ) -> Dict[int, Measurement]:
        """Measured time of a GO-library entry's picks: the isolated tile
        at CD 1 plus each tuned CD's GO tile at that CD."""
        cds = sorted(entry.go) if cds is None else sorted(cds)
        out = {1: self.measure_group(desc, entry.isolated, 1)}
        for cd in cds:
            if cd <= 1:
                continue
            out[cd] = self.measure_group(desc, entry.tile_for_cd(cd), cd)
        return out

    # ----------------------------------------------------------- re-rank
    def rerank(self, desc, entry, cds: Sequence[int] | None = None):
        """Measured re-rank of Step-② candidates (`tune_gemm(...,
        measure=)` / `tune_op(..., measure=)` hook, DESIGN.md §16).

        Per CD the candidate set is the modeled pick, the other CDs'
        picks, the isolated tile, and (GEMMs) the freshly re-derived
        Step-① RC winners; each is measured as the grouped launch the
        scheduler would emit and the measured-fastest wins.  Returns a
        new `GOEntry` carrying ``measured`` times + backend/sample/run-id
        provenance (persisted at schema v5); modeled speedups are kept —
        measured and modeled columns stay separately comparable."""
        from repro.core.tuner import tune_rc

        cds = sorted(entry.go) if cds is None else sorted(int(c) for c in cds)
        rc_winners: Dict[str, TileConfig] = {}
        if family_of(desc) == "gemm" and getattr(desc, "batch", 1) == 1:
            rc_winners = {
                name: tune_rc(desc, frac, self.spec)
                for name, frac in RC_FRACTIONS.items()
            }
        iso = self.measure_group(desc, entry.isolated, 1)
        measured: Dict[int, float] = {1: iso.time_s}
        new_go = dict(entry.go)
        new_src = dict(entry.rc_source)
        for cd in cds:
            if cd <= 1:
                continue
            cands: List[tuple[str, TileConfig]] = [
                (entry.rc_source.get(cd, "model"), entry.tile_for_cd(cd))
            ]
            for c, t in sorted(entry.go.items()):
                if c != cd:
                    cands.append((entry.rc_source.get(c, "model"), t))
            cands.append(("GPU", entry.isolated))
            cands += sorted(rc_winners.items())
            seen, uniq = set(), []
            for name, t in cands:
                if t not in seen:
                    seen.add(t)
                    uniq.append((name, t))
            best_name, best_tile, best = None, None, math.inf
            for name, t in uniq:
                m = self.measure_group(desc, t, cd)
                if m.time_s < best:        # strict: ties keep the modeled pick
                    best_name, best_tile, best = name, t, m.time_s
            new_go[cd] = best_tile
            new_src[cd] = best_name
            measured[cd] = best
        return dc_replace(
            entry, go=new_go, rc_source=new_src, measured=measured,
            measure_backend=self.backend, measure_samples=self.repeats,
            measure_run_id=_run_key(
                [desc.key()], [entry.isolated], 0, self.backend,
                self.warmup, self.repeats, self.seed))


# --------------------------------------------------------------- CLI smoke
def smoke_grid(cells: int = 4) -> List[GemmDesc]:
    """Deterministic small-GEMM grid for the CI ``measure-smoke`` step —
    decode-ish shapes that interpret mode times in well under a second."""
    shapes = [(8, 128, 128), (8, 256, 128), (16, 128, 256), (16, 256, 256),
              (32, 128, 128), (64, 128, 128), (8, 128, 256), (16, 128, 128)]
    return [GemmDesc(m, n, k, dtype="f32") for m, n, k in shapes[:cells]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="interpret-backend measurement smoke: time a small "
        "GEMM grid through the harness and fail on non-finite/zero "
        "timings (the CI tier-1 measure-smoke step)")
    ap.add_argument("--cells", type=int, default=4)
    ap.add_argument("--cd", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-sample watchdog deadline; over-deadline "
                    "samples count as hangs and are reported")
    args = ap.parse_args(argv)

    from repro.core.tuner import tune_gemm

    deadline_s = None if args.deadline_ms is None else args.deadline_ms * 1e-3
    measurer = Measurer(warmup=args.warmup, repeats=args.repeats,
                        deadline_s=deadline_s)
    bad = 0
    print(f"# backend={measurer.backend} warmup={args.warmup} "
          f"repeats={args.repeats} deadline_ms={args.deadline_ms}")
    print(f"{'desc':24} {'cd':>3} {'measured_us':>12} {'n':>3} "
          f"{'hangs':>5}  run_id")
    for desc in smoke_grid(args.cells):
        entry = tune_gemm(desc)
        for cd in (1, args.cd):
            m = measurer.measure_group(desc, entry.tile_for_cd(cd), cd)
            flag = "" if m.finite else "  <-- NOT FINITE/ZERO"
            print(f"{desc.key():24} {cd:>3} {m.time_s * 1e6:>12.1f} "
                  f"{m.n:>3} {m.hangs:>5}  {m.run_id}{flag}")
            if not m.finite:
                bad += 1
    print(f"# hangs={measurer.hangs}")
    if bad:
        print(f"::error::measure-smoke: {bad} non-finite/zero timing(s)")
        return 1
    print("# measure-smoke OK")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
