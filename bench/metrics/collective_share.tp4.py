"""Share of the decode step's device time spent in collective operations
(all-reduce, all-gather, reduce-scatter, permutes), per chip, averaged
over the chips of the mesh."""
from statistics import mean

from bench.lib import trace


def read(ctx, rec, t):
    shares = []
    for dev, spans in trace.per_device(t, r"decode_step", modules=True).items():
        total = sum(s.dur for s in spans)
        if not total:
            continue
        ops = trace.within(t.ops.get(dev, []), spans)
        coll = sum(e.dur for e in ops if trace.COLLECTIVE.search(e.name))
        shares.append(coll / total)
    return 100.0 * mean(shares) if shares else None
