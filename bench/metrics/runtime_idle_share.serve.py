"""Share of `idle_share.serve`'s stretch (from the first traced
`jit_decode_step` to the end of the traced window) in which the device
is idle while one of the runtime's `runtime.*` host spans is open: the
dispatch path holding the chip back.  Averaged over the chips.  It also
prints the stretch's idle time on the first chip split by the innermost
`serve.*`/`runtime.*` span open, and the host time of the `runtime.*`
spans per decode step of the stretch (their cost with the profiler on)."""
from statistics import mean

from bench.lib import attribution, trace


def read(ctx, rec, t):
    steps = [e.start for evs in trace.per_device(t, r"decode_step", modules=True).values()
             for e in evs]
    runtime = [h for h in t.host if h.name.startswith("runtime.")]
    if not steps or not runtime:
        return None
    lo, hi = max(min(steps), t.window[0]), t.window[1]
    if hi <= lo:
        return None
    dev = sorted(t.ops)[0]
    spans = [h for h in t.host if h.name.startswith(("serve.", "runtime."))]
    split = attribution.idle_by_span(t.ops[dev], spans, lo, hi)
    print(f"[runtime_idle_share] idle s of {hi - lo:.4f} s on {dev} by span: "
          + ", ".join(f"{k} {v:.6f}" for k, v in sorted(split.items(), key=lambda x: -x[1])),
          flush=True)
    n = sum(1 for e in t.modules[dev] if "decode_step" in e.name and e.start >= lo)
    inside = sum(h.dur for h in runtime if lo <= h.start < hi)
    print(f"[runtime_idle_share] runtime.* spans {1e6 * inside / max(n, 1):.1f} us per decode "
          f"step of the stretch ({n} steps)", flush=True)
    return 100.0 * mean(attribution.idle_under(evs, runtime, lo, hi) / (hi - lo)
                        for evs in t.ops.values())
