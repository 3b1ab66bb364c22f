"""Share of the traced decode steps' time in which no operation ran on the
device: from the start of the first `jit_decode_step` program in the trace
to the end of the traced stretch, averaged over the chips.  The per-call
lowering before it is `serve_compile_ms`'s."""
from bench.lib import trace


def read(ctx, rec, t):
    steps = [e.start for evs in trace.per_device(t, r"decode_step", modules=True).values()
             for e in evs]
    if not steps:
        return None
    v = trace.idle_share(t, since=min(steps))
    return None if v is None else 100.0 * v
