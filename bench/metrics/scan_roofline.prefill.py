"""Roofline time of the prefill's chunked SSD scans (`goldyloc_mamba_scan_c<L>`
inside `jit_prefill`, one a Mamba2 layer: `flops_hybrid.ssd_chunk` over
the prompt for the batch, every head, B and C per group) over their
device time, summed over the chips."""
import re

from bench.lib import flops_hybrid as fh, trace

CHUNK = re.compile(r"goldyloc_mamba_scan_c(\d+)")


def read(ctx, rec, t):
    m, tr = ctx.cell.model, ctx.cell.traffic
    H = m["ssm_expand"] * m["d_model"] // m["ssm_head_dim"] // ctx.cell.chips
    roof, dev = 0.0, 0.0
    for d, spans in trace.per_device(t, r"prefill", modules=True).items():
        for e in trace.matching(trace.within(t.ops.get(d, []), spans), CHUNK.pattern):
            f, b = fh.ssd_chunk(tr["batch"], H, m["ssm_ngroups"], tr["prompt_len"],
                                m["ssm_state"], m["ssm_head_dim"],
                                int(CHUNK.search(e.text or e.name).group(1)))
            roof += fh.roofline_s(f, b, ctx.peaks)
            dev += e.dur
    return 100.0 * roof / dev if dev else None
