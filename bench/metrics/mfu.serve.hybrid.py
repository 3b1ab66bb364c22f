"""Model FLOPs of the untraced batches of the window (each request's
prefill and its own generated tokens, `flops_hybrid.served_flops`) per
second of those batches on the host clock, over the cell's chips times
their peak bf16 rate.  The traced batch is left out: the profiler slows
it."""
from bench.lib import flops_hybrid as fh


def read(ctx, rec, t):
    m, P = ctx.cell.model, ctx.cell.traffic["prompt_len"]
    plain = [b for b in rec["batches"] if not b["traced"]]
    if not plain:
        return None
    work = sum(fh.served_flops(m, P, n) for b in plain for n in b["lens"])
    secs = sum(b["wall_s"] for b in plain)
    return 100.0 * work / secs / (ctx.cell.chips * ctx.peaks["bf16_flops"])
