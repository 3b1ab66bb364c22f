"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

Device planes are those named ``/device:TPU:<n>``.  On each, the device's
operations are the events of its ``XLA Ops`` line and its programs those
of its ``XLA Modules`` line.  An operation's event is named by its whole
HLO instruction (``%goldyloc_gemm_8x128x128.1 = bf16[...] custom-call(...)``)
and a program's by ``jit_<name>(<id>)``; both are cut to the bare name
(``goldyloc_gemm_8x128x128.1``, ``jit_decode_step``), so that a pattern
never matches an operand.  Host planes carry the spans the benchmark
writes with `jax.profiler.TraceAnnotation`; the traced window is the span
named ``bench.traced``.  Every time is in seconds on the trace's clock.

Operations are cut to the window; programs are kept only where they lie
wholly inside it, so that a program's time is never a part of a run.
Busy time is the union of a device's operation intervals inside the
window, so overlapping operations count once.  A kernel is found by a
pattern matched against the event's name and its string statistics
(``hlo_op``, ``long_name`` and the like), since the profiler may carry a
Pallas kernel's name in either.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|send|recv", re.I)


@dataclass
class Event:
    name: str
    start: float
    end: float
    text: str = ""          # bare name and short string stats, for matching

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # per device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # per device
    host: List[Event] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def bare(name: str) -> str:
    """``%op.1 = type op(...)`` -> ``op.1``; ``jit_f(123)`` -> ``jit_f``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def _events(line, lo: float = float("-inf"), hi: float = float("inf"),
            whole: bool = False) -> List[Event]:
    out = []
    for ev in line.events:
        s = ev.start_ns * 1e-9
        e = s + ev.duration_ns * 1e-9
        if e <= lo or s >= hi or (whole and (s < lo or e > hi)):
            continue
        name = bare(ev.name)
        texts = [name] + [v for _, v in (ev.stats or ())
                          if isinstance(v, str) and len(v) < 200]
        out.append(Event(name, max(s, lo), min(e, hi), " ".join(texts)))
    return out


def load(path: str) -> Trace:
    """Read ``path`` (a file, or a directory holding one `.xplane.pb`)."""
    from jax.profiler import ProfileData

    if not path.endswith(".xplane.pb"):
        found = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = sorted(found)[-1]
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    t = Trace()
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            t.host += _events(line)
    spans = [e for e in t.host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace {path}")
    t.window = (min(e.start for e in spans), max(e.end for e in spans))
    lo, hi = t.window
    for plane in planes:
        if not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE in lines:
            t.ops[plane.name] = _events(lines[OPS_LINE], lo, hi)
        if MODULES_LINE in lines:
            t.modules[plane.name] = _events(lines[MODULES_LINE], lo, hi, whole=True)
    return t


def union_s(events: List[Event]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for ev in sorted(events, key=lambda e: e.start):
        if cur_e is None or ev.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = ev.start, ev.end
        else:
            cur_e = max(cur_e, ev.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(t: Trace, since: float = float("-inf")) -> float:
    """Seconds in which an operation ran, from ``since`` to the end of the
    window, averaged over the devices."""
    if not t.ops:
        return 0.0
    return sum(union_s([Event(e.name, max(e.start, since), e.end)
                        for e in evs if e.end > since])
               for evs in t.ops.values()) / len(t.ops)


def idle_share(t: Trace, since: float | None = None) -> Optional[float]:
    """Share of the window, or of its part from ``since`` on, in which no
    operation ran."""
    lo = t.window[0] if since is None else max(since, t.window[0])
    span = t.window[1] - lo
    if not t.ops or span <= 0:
        return None
    return 1.0 - busy_s(t, lo) / span


def matching(events: List[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.text)]


def per_device(t: Trace, pattern: str, modules: bool = False) -> Dict[str, List[Event]]:
    src = t.modules if modules else t.ops
    return {dev: matching(evs, pattern) for dev, evs in src.items()}


def within(events: List[Event], spans: List[Event]) -> List[Event]:
    """The events that start inside one of ``spans``."""
    spans = sorted(spans, key=lambda s: s.start)
    out = []
    for e in events:
        for s in spans:
            if s.start <= e.start < s.end:
                out.append(e)
                break
    return out


CONTROL = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def top_ops(t: Trace, n: int = 10) -> List[list]:
    """The device operations that took most time, averaged per device, by
    name without its instance number.  Control-flow operations (a layer
    scan's ``while``) are left out: the operations of their bodies are
    events of their own."""
    acc: Dict[str, float] = {}
    for evs in t.ops.values():
        for e in evs:
            if CONTROL.match(e.name):
                continue
            key = re.sub(r"\.\d+$", "", e.name)
            acc[key] = acc.get(key, 0.0) + e.dur
    k = max(len(t.ops), 1)
    return [[name, s / k] for name, s in sorted(acc.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(t: Trace, n: int = 10) -> List[list]:
    """The longest gaps between operations on the first device, each named
    by the shortest host span that covers its middle (what the host was
    doing), or "none"."""
    if not t.ops:
        return []
    dev = sorted(t.ops)[0]
    evs = sorted(t.ops[dev], key=lambda e: e.start)
    gaps, cur = [], t.window[0]
    for e in evs:
        if e.start > cur:
            gaps.append((cur, e.start))
        cur = max(cur, e.end)
    if t.window[1] > cur:
        gaps.append((cur, t.window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        cover = [h for h in t.host if h.start <= mid <= h.end and h.name != WINDOW_SPAN]
        label = min(cover, key=lambda h: h.dur).name if cover else "none"
        out.append([label, e - s])
    return out
