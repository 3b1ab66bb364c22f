"""Bytes a decode step must read on one chip (its share of the weights and
of the whole fixed-size cache, `flops.decode_step_bytes`) at the peak HBM
bandwidth, over the device time of one `jit_decode_step` program, averaged
over the traced steps and the chips."""
from statistics import mean

from bench.lib import flops, trace


def read(ctx, rec, t):
    steps = [e.dur for evs in trace.per_device(t, r"decode_step", modules=True).values()
             for e in evs]
    if not steps:
        return None
    m, tr = ctx.cell.model, ctx.cell.traffic
    nbytes = flops.decode_step_bytes(m, tr["batch"], rec["s_max"], ctx.cell.chips)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_s"] / mean(steps)
