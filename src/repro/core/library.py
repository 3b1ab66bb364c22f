"""GO GEMM library — paper §4.2.2 (DESIGN.md §3).

The baseline library maps a GEMM input to its isolated-tuned kernel; the GO
library additionally returns, per concurrency degree, a pointer to the
globally-optimized kernel (our TileConfig ↔ the paper's kernel object).
JSON-persistent so the one-time tuning cost is amortized, exactly like a
vendor BLAS tuning cache.

The on-disk blob is versioned (``SCHEMA_VERSION``): v2 added the split-K
axis to persisted tiles (4-element lists) and wrapped entries under a
``{"schema": 2, "entries": ...}`` envelope; v3 (DESIGN.md §14) added the
per-entry ``family`` field for the heterogeneous kernel zoo; v4
(DESIGN.md §15) adds the Stream-K axis to persisted tiles (5-element
lists ``[bm, bn, bk, split_k, stream_k]``) and switches `save` to
compact JSON (no indent, tight separators — committed libraries carry
hundreds of entries and the pretty form was ~2× the bytes for a blob
only machines read); v5 (DESIGN.md §16) adds *optional* measured-time
provenance per entry (``measured`` CD→seconds map + backend tag, sample
count, timestamp-free run id from `core/measure.py`) — modeled-only
entries serialize exactly as at v4, and the planner never consults the
measured fields, so a v5 blob read by modeled-only logic plans
identically.  Loading is backward compatible with version-appropriate
trust:

- a bare v1 blob parses, but its entries were tuned on a pre-split-K
  search space — stale, so they are **discarded** with a warning and
  re-tuned lazily;
- v2/v3/v4 blobs' entries were tuned on the *same GEMM search space*
  later versions widen (Stream-K adds candidates without perturbing the
  old ones, the argmin tie-break is strict, and v5 adds no candidates
  at all), so they are **preserved bitwise** — short tile lists default
  ``stream_k=0`` (and v2 the family ``"gemm"``); measured fields
  default empty; a migration warning notes the rewrite that the next
  `save` performs.
"""
from __future__ import annotations

import json
import os
import threading
import warnings
from dataclasses import dataclass, field, replace as dc_replace
from pathlib import Path
from typing import Dict, FrozenSet, Optional, Sequence

from repro.core.cost_model import TPUSpec, device_spec
from repro.core.gemm_desc import GemmDesc
from repro.core.tuner import CDS, GOEntry, tune_gemm, tune_op
from repro.kernels.gemm.ops import TileConfig

# Bump whenever the persisted format OR the tuning search space changes in
# a way that invalidates stored entries (v2: split-K axis + bm 8-32 rows;
# v3: per-entry kernel family; v4: Stream-K axis + compact JSON; v5:
# optional measured-time provenance — v2/v3/v4 entries stay valid).
SCHEMA_VERSION = 5


def _tile_to_list(t: TileConfig) -> list[int]:
    return [t.bm, t.bn, t.bk, t.split_k, t.stream_k]


def _tile_from_list(v) -> TileConfig:
    # 3-element (v1) lists default split_k=1; ≤4-element (v2/v3) lists
    # default stream_k=0 — both exact, so migration is bitwise.
    return TileConfig(*v)


class GOLibrary:
    """Thread-safe, lazily-tuned, optionally disk-backed kernel library."""

    def __init__(
        self,
        path: str | os.PathLike | None = None,
        spec: TPUSpec | None = None,
    ):
        self.path = Path(path) if path else None
        self.spec = device_spec() if spec is None else spec
        self._entries: Dict[str, GOEntry] = {}
        self._lock = threading.Lock()
        self.loaded_schema: Optional[int] = None
        # Runtime quarantine state (DESIGN.md §18.3): per desc key, the
        # tile keys the circuit breaker has banned.  NOT persisted by
        # `save` — quarantine reflects live failures on this process's
        # backend, not a property of the tuned library.
        self._quarantine: Dict[str, set] = {}
        if self.path and self.path.exists():
            self.load(self.path)

    # -------------------------------------------------------------- access
    def get(self, desc) -> GOEntry:
        """GO entry for any `OpDesc` family — GEMMs take the batched
        `tune_gemm` path, other families `tune_op` (§14).  Entries are
        filtered through the quarantine set on the way out (§18.3), so
        neither the planner nor the tuner can hand back a banned tile."""
        key = desc.key()
        with self._lock:
            e = self._entries.get(key)
        if e is not None:
            return self._sanitize(key, e)
        e = (tune_gemm(desc, self.spec) if isinstance(desc, GemmDesc)
             else tune_op(desc, self.spec))
        with self._lock:
            self._entries.setdefault(key, e)
        return self._sanitize(key, self._entries[key])

    def tile(self, desc, cd: int = 1) -> TileConfig:
        return self.get(desc).tile_for_cd(cd)

    def prewarm(self, descs: Sequence) -> int:
        """Tune ahead of traffic (DESIGN.md §10): the serving runtime calls
        this with the ops a workload is about to issue so the one-time RC
        tuning cost never lands on a live request.  Missing GEMMs are
        tuned in ONE `tune_gemm_batch` sweep (the whole pool broadcasts
        through the cost model, DESIGN.md §13); other families go through
        `tune_op` per descriptor (their tile spaces are tiny, §14).
        Returns the number of newly tuned entries."""
        from repro.core.tuner import tune_gemm_batch

        with self._lock:
            missing: Dict[str, object] = {
                d.key(): d for d in descs if d.key() not in self._entries
            }
        if missing:
            gemms = [d for d in missing.values() if isinstance(d, GemmDesc)]
            others = [d for d in missing.values()
                      if not isinstance(d, GemmDesc)]
            entries = tune_gemm_batch(gemms, self.spec)
            entries += [tune_op(d, self.spec) for d in others]
            with self._lock:
                for e in entries:
                    self._entries.setdefault(e.desc_key, e)
        fresh = len(missing)
        if fresh and self.path:
            self.save()
        return fresh

    def invalidate(self, keys: Sequence[str]) -> int:
        """Drop entries by desc key so the next `get`/`prewarm` re-tunes
        them — the drift re-tune path (DESIGN.md §16): the runtime queues
        stale classes' descs, invalidates, and prewarms off the dispatch
        path.  Returns the number of entries actually dropped."""
        n = 0
        with self._lock:
            for k in keys:
                if self._entries.pop(k, None) is not None:
                    n += 1
        return n

    # --------------------------------------------------- quarantine (§18.3)
    def quarantine(self, keys: Sequence[str], tile_key: str) -> None:
        """Ban ``tile_key`` for the given desc keys: `get` (and hence
        `tile`, the tuner memo rebuilds, and plan derivation) substitutes
        the isolated tile for banned GO picks and drops their speedup
        claims, so ``preferred_cd`` stops trusting the quarantined
        kernel.  Paired with `GOLibrary.invalidate` by the circuit
        breaker so even a re-tune cannot resurrect the tile until
        `release`."""
        with self._lock:
            for k in keys:
                self._quarantine.setdefault(k, set()).add(tile_key)

    def release(self, keys: Sequence[str], tile_key: str) -> None:
        """Lift a quarantine (half-open probe, `Runtime.process_retunes`)."""
        with self._lock:
            for k in keys:
                s = self._quarantine.get(k)
                if s is not None:
                    s.discard(tile_key)
                    if not s:
                        del self._quarantine[k]

    def quarantined(self) -> Dict[str, FrozenSet[str]]:
        with self._lock:
            return {k: frozenset(s) for k, s in self._quarantine.items()}

    def _sanitize(self, key: str, e: GOEntry) -> GOEntry:
        """Apply the quarantine set to one entry on the read path: banned
        GO tiles degrade to the isolated tile and lose their speedup
        entry (no stale >1 claim keeps electing the banned CD).  The
        isolated tile itself is never substituted — it is the ladder's
        legacy rung, and correctness ultimately rests on the reference
        rung, not on isolated being healthy."""
        banned = self._quarantine.get(key)
        if not banned:
            return e
        go = {cd: (e.isolated if t.key() in banned else t)
              for cd, t in e.go.items()}
        speedup = {cd: s for cd, s in e.speedup.items()
                   if e.go[cd].key() not in banned}
        if go == e.go and speedup == e.speedup:
            return e
        return dc_replace(e, go=go, speedup=speedup)

    def __len__(self) -> int:
        return len(self._entries)

    def entries(self) -> Dict[str, GOEntry]:
        return dict(self._entries)

    # ----------------------------------------------------------- persist
    def save(self, path: str | os.PathLike | None = None) -> None:
        path = Path(path or self.path)

        def _rec(e: GOEntry) -> dict:
            rec = {
                "family": e.family,
                "isolated": _tile_to_list(e.isolated),
                "go": {str(cd): _tile_to_list(t) for cd, t in e.go.items()},
                "rc_source": e.rc_source,
                "speedup": {str(cd): s for cd, s in e.speedup.items()},
            }
            # v5 measured provenance is *optional*: modeled-only entries
            # keep the exact v4 record shape (byte-stable libraries).
            if e.measured:
                rec["measured"] = {str(cd): t for cd, t in e.measured.items()}
                rec["measure"] = {
                    "backend": e.measure_backend,
                    "samples": e.measure_samples,
                    "run_id": e.measure_run_id,
                }
            return rec

        blob = {
            "schema": SCHEMA_VERSION,
            "entries": {k: _rec(e) for k, e in self._entries.items()},
        }
        tmp = path.with_suffix(".tmp")
        # Compact serialization (satellite of DESIGN.md §15): committed
        # libraries are machine-read only, so drop the indent and the
        # default ", "/": " separator padding.
        tmp.write_text(json.dumps(blob, separators=(",", ":")))
        tmp.replace(path)

    def load(self, path: str | os.PathLike) -> int:
        """Parse a v1–v5 blob; returns the file's schema version (0 when
        the file is unusable).

        Crash-safe (DESIGN.md §18.4): a corrupt, truncated, or
        wrong-type blob — the startup equivalent of a bad kernel — warns
        and leaves the library EMPTY instead of raising, so the server
        boots and re-tunes lazily exactly as if the cache file had never
        existed.

        v1 entries are *discarded* (tuned on the pre-split-K search space
        — they would mis-plan, DESIGN.md §13) and re-tuned lazily.
        v2/v3/v4 entries are *preserved bitwise* — short tile lists
        default ``stream_k=0`` (and v2 the family ``"gemm"``); v4 only
        widened the Step-② candidate set with a strict tie-break, and v5
        only *annotates* entries with optional measured provenance
        (DESIGN.md §15/§16), so old picks remain exactly what the
        current tuner would keep — a migration warning notes that the
        next `save` rewrites the file at v5."""
        def _unusable(why: str) -> int:
            warnings.warn(
                f"GO library {path} is unusable ({why}); starting with an "
                "empty library — entries re-tune lazily and the next save "
                "rewrites the file.", stacklevel=3)
            self.loaded_schema = None
            return 0

        try:
            blob = json.loads(Path(path).read_text())
        except (OSError, UnicodeDecodeError, ValueError) as e:
            # json.JSONDecodeError ⊂ ValueError: corrupt/truncated file.
            return _unusable(f"{type(e).__name__}: {e}")
        if isinstance(blob, dict) and "schema" in blob:
            try:
                schema = int(blob["schema"])
            except (TypeError, ValueError):
                return _unusable(f"non-integer schema {blob['schema']!r}")
            entries = blob.get("entries")
        else:
            schema, entries = 1, blob           # bare v1 mapping
        if not isinstance(entries, dict):
            return _unusable(
                f"entries is {type(entries).__name__}, expected mapping")
        self.loaded_schema = schema
        if schema < 2:
            warnings.warn(
                f"GO library {path} has stale schema v{schema} (< "
                f"v{SCHEMA_VERSION}); discarding {len(entries)} entries — "
                "they will be re-tuned on the current search space.",
                stacklevel=2,
            )
            return schema
        if schema < SCHEMA_VERSION:
            warnings.warn(
                f"GO library {path} has schema v{schema} (< "
                f"v{SCHEMA_VERSION}); migrating {len(entries)} entries "
                "in place (GEMM family default) — the next save rewrites "
                f"the file at v{SCHEMA_VERSION}.",
                stacklevel=2,
            )
        bad = 0
        for k, v in entries.items():
            try:
                meta = v.get("measure", {})
                self._entries[k] = GOEntry(
                    desc_key=k,
                    isolated=_tile_from_list(v["isolated"]),
                    go={int(cd): _tile_from_list(t)
                        for cd, t in v["go"].items()},
                    rc_source={int(c): s
                               for c, s in v.get("rc_source", {}).items()},
                    speedup={int(c): s
                             for c, s in v.get("speedup", {}).items()},
                    family=v.get("family", "gemm"),
                    measured={int(c): float(t)
                              for c, t in v.get("measured", {}).items()},
                    measure_backend=meta.get("backend"),
                    measure_samples=int(meta.get("samples", 0)),
                    measure_run_id=meta.get("run_id"),
                )
            except (AttributeError, KeyError, TypeError, ValueError):
                bad += 1       # malformed record — skip, re-tune lazily
        if bad:
            warnings.warn(
                f"GO library {path}: skipped {bad} malformed entr"
                f"{'y' if bad == 1 else 'ies'} — they re-tune lazily.",
                stacklevel=2)
        return schema


_DEFAULT: Optional[GOLibrary] = None


def default_library() -> GOLibrary:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = GOLibrary()
    return _DEFAULT
