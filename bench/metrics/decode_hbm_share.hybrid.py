"""Bytes a zamba2 decode step must move (`flops_hybrid.decode_step_bytes`:
the Mamba2 layers' weights and state, a shared block's weights at each
use, the uses' own weights, the head, the K/V cache at its capacity) at
the peak HBM bandwidth, over the device time of one `jit_decode_step`
program, averaged over the traced steps and the chips."""
from statistics import mean

from bench.lib import flops_hybrid as fh, trace


def read(ctx, rec, t):
    steps = [e.dur for evs in trace.per_device(t, r"decode_step", modules=True).values()
             for e in evs]
    if not steps:
        return None
    m, B = ctx.cell.model, ctx.cell.traffic["batch"]
    fh.check_cache(m, B, rec["s_max"], rec["cache_bytes"])
    nbytes = fh.decode_step_bytes(m, B, rec["s_max"])
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_s"] / mean(steps)
