"""Roofline time of the prefill's flash-attention kernels
(`goldyloc_flash_*` inside `jit_prefill`; causal attention over the
prompt, `flops.flash`, for the heads one chip holds) over their device
time, summed over the chips."""
from bench.lib import flops, trace


def read(ctx, rec, t):
    m, tr, chips = ctx.cell.model, ctx.cell.traffic, ctx.cell.chips
    f, b = flops.flash(tr["batch"], m["n_heads"] // chips, m["n_kv_heads"] // chips,
                       tr["prompt_len"], m["head_dim"])
    roof, dev = 0.0, 0.0
    for d, spans in trace.per_device(t, r"prefill", modules=True).items():
        evs = trace.matching(trace.within(t.ops.get(d, []), spans), r"goldyloc_flash")
        roof += len(evs) * flops.roofline_s(f, b, ctx.peaks)
        dev += sum(e.dur for e in evs)
    return 100.0 * roof / dev if dev else None
