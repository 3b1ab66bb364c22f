"""Device time attributed to the program's own names.

Two maps, each from what the trace names to what the program names:

- An operation's scope.  The profiler names a device operation by its HLO
  instruction (``copy.3``); the `jax.named_scope` path it was traced
  under is in that instruction's ``metadata={op_name=...}`` in the
  compiled module's text (`Compiled.as_text()`), which the trace does not
  carry.  `op_scopes` reads the text; `decode_program_text` compiles the
  decode step as `greedy_decode` does, from shapes alone, so that its
  instruction names are those of the traced program.
- An idle stretch's host span.  Host spans (`jax.profiler.TraceAnnotation`)
  share the device trace's clock; `idle_under` measures the device's idle
  time inside a set of spans, and `idle_by_span` splits it by the
  innermost span open.

Where the program writes no such scope or span, the functions find
nothing, and the metrics that read them report nothing.
"""
from __future__ import annotations

import bisect
import contextlib
import re
from typing import Dict, Iterable, List, Tuple

from bench.lib import trace

INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Every instruction of a compiled module's text, by name, to the
    ``op_name`` of its metadata ("" where it has none)."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m is None:
            continue
        op = OP_NAME.search(m.group(2))
        out[m.group(1)] = op.group(1) if op else ""
    return out


def region(op_name: str) -> str:
    """Where in a decode step an operation of scope ``op_name`` lies:
    ``block`` (a layer's own work), ``scan`` (the layer scan's slicing,
    relayout and write-back around the blocks: under ``layers``, outside
    ``block``), ``step`` (outside the scan: embedding, head, sampling
    inputs) or ``none`` (no scope)."""
    parts = op_name.split("/")
    if "block" in parts:
        return "block"
    if "layers" in parts:
        return "scan"
    return "step" if op_name else "none"


def decode_program_text(model, params, batch: int, prompt_len: int, s_max: int,
                        cache_dtype) -> str:
    """The compiled text of ``model.decode_step`` as `greedy_decode`
    compiles it, from shapes alone.  ``params`` are `ShapeDtypeStruct`s
    carrying the served weights' shardings; the prompt and the fresh cache
    are uncommitted, as the serve loop makes them; the cache, length and
    first token take the shardings that the compiled prefill and the
    eager argmax give them.  The decode step is compiled afresh (see
    `_uncached`); its instruction names are those of the executable that
    ran wherever that was compiled from the same tree."""
    import jax
    import jax.numpy as jnp

    def sds(x, sharding=None):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    prompt = {"tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)}
    cache = jax.eval_shape(lambda: model.init_cache(batch=batch, s_max=s_max,
                                                    dtype=cache_dtype))
    prefill = jax.jit(model.prefill).lower(params, prompt, cache).compile()
    logits, cache, length = jax.eval_shape(model.prefill, params, prompt, cache)
    logits_sh, cache_sh, length_sh = prefill.output_shardings
    argmax = jax.jit(lambda lg: jnp.argmax(lg[:, -1], axis=-1)[:, None])
    logits = sds(logits, logits_sh)
    tok = sds(jax.eval_shape(argmax, logits),
              argmax.lower(logits).compile().output_shardings)
    cache = jax.tree.map(sds, cache, cache_sh)
    length = jax.ShapeDtypeStruct((), jnp.int32, sharding=length_sh)
    lowered = jax.jit(model.decode_step).lower(params, tok, cache, length)
    with _uncached():
        return lowered.compile().as_text()


@contextlib.contextmanager
def _uncached():
    """JAX's persistent compilation cache off for the compiles inside.
    Its key leaves out the metadata, so a cached executable may carry the
    scopes of another tree that compiled the same program."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def decode_ops(t: trace.Trace):
    """Per device: the traced decode programs (`jit_decode_step`) and the
    operations inside them, control flow (a scan's ``while``) left out."""
    out = {}
    for dev, spans in trace.per_device(t, r"decode_step", modules=True).items():
        if spans:
            ops = [e for e in trace.within(t.ops.get(dev, []), spans)
                   if not trace.CONTROL.match(e.name)]
            out[dev] = (spans, ops)
    return out


def time_by_region(spans, ops, scopes: Dict[str, str]) -> Dict[str, float]:
    """Shares of the programs' device time by `region`; operations the
    text lacks count under ``missing``."""
    total = sum(s.dur for s in spans)
    acc: Dict[str, float] = {}
    for e in ops:
        key = region(scopes[e.name]) if e.name in scopes else "missing"
        acc[key] = acc.get(key, 0.0) + e.dur
    return {k: v / total for k, v in acc.items()} if total else {}


def _merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(ops: List[trace.Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no operation ran."""
    out, cur = [], lo
    for s, e in _merge((max(o.start, lo), min(o.end, hi)) for o in ops if o.end > lo
                       and o.start < hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def idle_under(ops: List[trace.Event], spans: List[trace.Event], lo: float,
               hi: float) -> float:
    """Seconds of [lo, hi] in which no operation ran and one of ``spans``
    was open."""
    covered = _merge((s.start, s.end) for s in spans)
    total, j = 0.0, 0
    for gs, ge in gaps(ops, lo, hi):
        while j < len(covered) and covered[j][1] <= gs:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < ge:
            total += max(0.0, min(ge, covered[k][1]) - max(gs, covered[k][0]))
            k += 1
    return total


def idle_by_span(ops: List[trace.Event], spans: List[trace.Event], lo: float,
                 hi: float) -> Dict[str, float]:
    """The idle seconds of [lo, hi] by the innermost of ``spans`` open at
    each moment ("none" where none is)."""
    spans = sorted(spans, key=lambda s: s.start)
    starts = [s.start for s in spans]
    acc: Dict[str, float] = {}
    for gs, ge in gaps(ops, lo, hi):
        # spans that overlap the gap; a span's start may lie long before it
        cands = [s for s in spans[:bisect.bisect_left(starts, ge)] if s.end > gs]
        cuts = sorted({gs, ge, *(c for s in cands for c in (s.start, s.end) if gs < c < ge)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_ = [s for s in cands if s.start <= mid < s.end]
            name = min(open_, key=lambda s: s.dur).name if open_ else "none"
            acc[name] = acc.get(name, 0.0) + (b - a)
    return acc
